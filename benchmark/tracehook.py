"""Profiler options for the verify tile's device trace in `--trace 1` runs.

The tile starts `jax.profiler.start_trace` itself when
FDTPU_JAX_TRACE_DIR is set, with the default options, which record every
Python call of the process: that slows the tile's host loop several times
over and writes hundreds of MiB.  Installed in the tile's process (run.py
does so as spawn re-imports it there), this turns the Python and host
tracers off and asks the TPU tracer for XLA operations alone, all that
reduce.py reads; where the runtime refuses that mode, the trace starts
without it.  The device events the profiler keeps are still bounded: on
a TPU v5e under the firehose they run out 1.4-1.9 s into the window
(reduce.covered).
"""

TPU_TRACE_MODE = "TRACE_ONLY_XLA"


def install() -> None:
    import jax

    start = jax.profiler.start_trace

    def options(tpu_mode: bool):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        if tpu_mode:
            opts.advanced_configuration = {"tpu_trace_mode": TPU_TRACE_MODE}
        return opts

    def start_trace(log_dir, *args, **kw):
        if args or kw.get("profiler_options") is not None:
            return start(log_dir, *args, **kw)
        try:
            return start(log_dir, profiler_options=options(True), **kw)
        except Exception:   # the mode is unknown to this runtime
            return start(log_dir, profiler_options=options(False), **kw)

    jax.profiler.start_trace = start_trace
