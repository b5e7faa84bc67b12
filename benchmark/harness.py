"""One run of one cell.

Boots the cell's topology through the program's own builder
(app/config.py build_topology, disco/run.py TopoRun), with one extra
consumer on the dedup tile's output link (observer.py).  Makes the mix's
traffic in worker processes while the topology boots, sends it as UDP
datagrams, one transaction each, to the net tile's TPU port, and keeps
every send time and every verdict seen.  This process never starts a JAX
backend: the verify tile owns the chip.
"""

import math
import multiprocessing as mp
import os
import socket
import tempfile
import time
import uuid
from dataclasses import dataclass, field

import numpy as np

from . import gen
from .observer import OBSERVED_LINK

OBS = "bench_obs"
VERIFY = "verify:0"
BOOT_TIMEOUT_S = 1000.0
DRAIN_GRACE_S = 60.0
SPAN_POLL_S = 0.05


def use_cache_dir(root) -> None:
    """Keep JAX's persistent compile cache at <root>/.xla_cache: a fixed
    path inside the checkout (the path is part of an entry's key).  The
    program writes into the directory the environment names but does not
    create it."""
    path = os.path.join(str(root), ".xla_cache")
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    # no size limit: with one, JAX's cache reads an access-time file for
    # every entry on each write, and one missing file makes every later
    # write fail, so each run compiled again
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


class NoDevice(RuntimeError):
    """The verify tile does not run on the accelerator the cell needs."""


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


def topology_config(overlay: dict, extra: dict | None = None) -> dict:
    """The program's defaults with the configuration's overlay on top
    (environment overrides are not read: a run depends on its files)."""
    from firedancer_tpu.app import config as config_mod
    cfg = config_mod.load(None, environ={})
    cfg = _merge(cfg, overlay)
    if extra:
        cfg = _merge(cfg, extra)
    return cfg


def _bench_run_class():
    from firedancer_tpu.disco.run import TopoRun

    from . import observer

    class BenchRun(TopoRun):
        """The program's supervisor, with the observer in the place of the
        tile the spec names OBS."""

        def __init__(self, spec, obs_args):
            self._obs_args = obs_args
            super().__init__(spec)

        def _spawn(self, name, restart_cnt=0):
            if name != OBS:
                return super()._spawn(name, restart_cnt)
            p = self._mpctx.Process(
                target=observer.main,
                args=(self.spec, name) + self._obs_args,
                name=f"fdtpu:{name}", daemon=True)
            p.start()
            self.procs[name] = p
            self._boot_deadline[name] = (time.monotonic()
                                         + self.policy.boot_grace_s)

    return BenchRun


def bucket_shapes(cfg: dict) -> list:
    """[batch, message width] of each verify bucket, in bucket order, as
    the topology builder sets them up."""
    from firedancer_tpu.tango.ring import packed_row_ml
    v = cfg["tiles"]["verify"]
    if int(cfg["quic"]["packed_publish"]):
        return [[int(v["batch"]), packed_row_ml(int(v["msg_maxlen"]))]]
    return [[int(b), int(m)] for b, m in
            (v.get("buckets") or [[v["batch"], v["msg_maxlen"]]])]


def _with_observer(spec):
    from firedancer_tpu.disco.topo import InLink, TileSpec, TopoSpec
    obs = TileSpec(OBS, "sink", (InLink(OBSERVED_LINK),), ())
    return TopoSpec(spec.app, spec.links, spec.tiles + (obs,),
                    spec.wksp_mb).validate()


def _await_trace(run, trace_dir: str, timeout_s: float = 180.0) -> None:
    """Raise HALT and wait until the verify tile has written its trace
    (the file exists and its size holds for a second).  The tile's exit
    after that, the TPU runtime's shutdown, can take minutes; the halt
    that follows ends it."""
    import glob

    from firedancer_tpu.tango.ring import Cnc
    for cnc in run.jt.cnc.values():
        cnc.signal(Cnc.SIGNAL_HALT)
    pattern = os.path.join(trace_dir, "plugins", "profile", "*",
                           "*.xplane.pb")
    deadline = time.monotonic() + timeout_s
    last = -1
    while time.monotonic() < deadline:
        sizes = [os.path.getsize(p) for p in glob.glob(pattern)]
        if sizes and sizes[-1] == last:
            return
        last = sizes[-1] if sizes else -1
        time.sleep(1.0)


@dataclass
class RunRecord:
    """What one run saw; every time is CLOCK_MONOTONIC in ns."""
    traffic: gen.Traffic
    loop: str
    send_pool: np.ndarray           # pool index of each send
    send_due: np.ndarray            # due time (open loop) or send time
    send_t: np.ndarray              # time each send left this process
    send_outcome: np.ndarray
    w0: int
    w1: int
    w0_real: int                    # the window on the realtime clock
    w1_real: int
    obs: dict                       # observer: t, tag, digest
    counters: dict                  # "w0" | "w1" | "end" -> tile -> dict
    spans: dict = field(default_factory=dict)   # tile -> span records
    trace_dir: str | None = None
    setup_s: float = 0.0
    t_end: int = 0                  # when the wait for verdicts ended
    buckets: list = field(default_factory=list)  # lanes per bucket index


def _span_poller(jt, tiles):
    cursors = {t: 0 for t in tiles}
    acc = {t: [] for t in tiles}

    def poll():
        for t in tiles:
            cur, recs = jt.trace[t].snapshot(cursors[t])
            cursors[t] = cur
            if len(recs):
                acc[t].append(recs)
    return poll, acc


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        require_tpu: bool = True, topo_extra: dict | None = None,
        log=print) -> RunRecord:
    """Boot, send, observe, halt.  Raises NoDevice when the verify tile
    is not on a TPU (and require_tpu), RuntimeError when a tile dies."""
    from firedancer_tpu.app import config as config_mod

    mix = cell.mix
    cfg = topology_config(cell.config["topology"], topo_extra)
    # a workspace name of this run alone: no two runs, of one checkout or
    # of two, can meet in /dev/shm, and run.close() unlinks only this one
    cfg["name"] = "bench" + uuid.uuid4().hex
    cfg["net"]["listen_port"] = 0
    depth = max(int(cfg["tiles"]["dedup"]["tcache_depth"]),
                int(cfg["tiles"]["verify"]["tcache_depth"]))
    pool_min = math.ceil(float(mix.get("pool_over_tcache", 0)) * depth)
    pool, sched, nkeys = gen.plan(mix, seed, seconds, pool_min)
    npool = len(pool.nsig)
    nworkers = max(1, min(8, (os.cpu_count() or 2) // 2))
    ctx = mp.get_context("spawn")
    workers = ctx.Pool(nworkers)
    try:
        pubs = gen.key_pubs(seed, nkeys)
        parts = workers.map_async(gen.build_slice, [
            (pool.slice(lo, hi), pubs)
            for lo, hi in gen.slices(npool, nworkers * 4)])
        trace_dir = None
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            os.environ["FDTPU_JAX_TRACE_DIR"] = trace_dir
        spec = _with_observer(config_mod.build_topology(cfg))
        count = ctx.RawValue("Q", 0)
        report = ctx.Event()
        rx_end, tx_end = ctx.Pipe(duplex=False)
        run = _bench_run_class()(spec, (count, report, tx_end))
        run.spec_cfg = cfg
        try:
            return _drive(run, mix, pool, sched, parts, count, report,
                          rx_end, seconds, trace, trace_dir, t_start,
                          require_tpu, log)
        finally:
            if trace_dir:
                _await_trace(run, trace_dir)
            run.halt(timeout=20.0)
            run.close()
            os.environ.pop("FDTPU_JAX_TRACE_DIR", None)
    finally:
        workers.terminate()
        workers.join()


def _drive(run, mix, pool, sched, parts, count, report, rx_end,
           seconds, trace, trace_dir, t_start, require_tpu,
           log) -> RunRecord:
    from firedancer_tpu.disco.metrics import device_platform_name

    run.wait_ready(timeout=BOOT_TIMEOUT_S)
    v = run.metrics(VERIFY)
    platform = device_platform_name(v["device_platform"])
    log(f"bench: topology up at {time.monotonic() - t_start:.1f} s, "
        f"verify tile on {platform} x{v['device_cnt']}")
    if require_tpu and platform != "tpu":
        raise NoDevice(f"the verify tile runs on {platform}, not a TPU")
    traffic = gen.assemble_traffic(pool, sched, parts.get(timeout=600))
    log(f"bench: traffic ready at {time.monotonic() - t_start:.1f} s")
    port = run.metrics("net")["bound_port"]
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4 << 20)
    sock.connect(("127.0.0.1", int(port)))
    jt = run.jt
    poll_spans, spans = _span_poller(jt, [VERIFY] if trace else [])
    net_m = jt.metrics["net"]
    state = {"last_check": 0}

    def periodic(now):
        if now - state["last_check"] < SPAN_POLL_S * 1e9:
            return
        state["last_check"] = now
        poll_spans()
        bad = run.poll()
        if bad is not None:
            raise RuntimeError(f"tile {bad} died during the run")

    counters = {}
    warm = float(mix["warmup_s"])
    try:
        if mix["loop"] == "open":
            rec = _open_loop(sock, traffic, warm, seconds, jt, counters,
                             periodic)
        else:
            rec = _closed_loop(sock, traffic, mix, warm, seconds, jt,
                               counters, count, net_m, periodic)
    finally:
        sock.close()
    send_pool, send_due, send_t, send_outcome, w0, w1, w0r, w1r = rec
    # every verdict of a send in the window may take up to the grace
    # period to arrive; a sound run finishes far sooner
    want = int((send_outcome == gen.PASS).sum())
    deadline = time.monotonic() + DRAIN_GRACE_S
    last, last_t = -1, time.monotonic()
    while count.value < want and time.monotonic() < deadline:
        if count.value != last:
            last, last_t = count.value, time.monotonic()
        elif time.monotonic() - last_t > 15.0:
            break
        periodic(time.monotonic_ns())
        time.sleep(0.01)
    time.sleep(0.05)
    t_end = time.monotonic_ns()
    poll_spans()
    counters["end"] = _snapshot(run)
    report.set()
    if not rx_end.poll(60.0):
        raise RuntimeError("the observer sent no record")
    obs = rx_end.recv()
    return RunRecord(
        traffic, mix["loop"], send_pool, send_due, send_t, send_outcome,
        w0, w1, w0r, w1r, obs, counters,
        {t: (np.concatenate(r) if r else None) for t, r in spans.items()},
        trace_dir, w0 / 1e9 - t_start, t_end,
        [b for b, _ in bucket_shapes(run.spec_cfg)])


def _snapshot(run) -> dict:
    return _snapshot_jt(run.jt)


def _snapshot_jt(jt) -> dict:
    return {name: blk.snapshot() for name, blk in jt.metrics.items()}


def _spin_until(t_ns: int):
    now = time.monotonic_ns()
    while now < t_ns:
        if t_ns - now > 2_000_000:
            time.sleep((t_ns - now - 1_000_000) / 1e9)
        now = time.monotonic_ns()
    return now


def _open_loop(sock, traffic, warm, seconds, jt, counters, periodic):
    """Sends on the schedule, however late the system runs: each send's
    latency counts from when it was due."""
    n = len(traffic.send_pool)
    offs = traffic.offs.tolist()
    pidx = traffic.send_pool.tolist()
    mv = memoryview(traffic.wires)
    t0 = time.monotonic_ns() + 50_000_000
    due = (traffic.send_due * 1e9).astype(np.int64) + t0
    due_l = due.tolist()
    sent = [0] * n
    w0 = t0 + int(warm * 1e9)
    w1 = w0 + int(seconds * 1e9)
    w0r = w1r = 0
    in_window = False
    send = sock.send
    mono = time.monotonic_ns
    for s in range(n):
        d = due_l[s]
        now = mono()
        if now < d:
            now = _spin_until(d)
        if not in_window and now >= w0:
            in_window = True
            counters["w0"] = _snapshot_jt(jt)
            w0r = time.time_ns() - (mono() - w0)
        p = pidx[s]
        send(mv[offs[p]:offs[p + 1]])
        sent[s] = now
        if (s & 255) == 0:
            periodic(now)
    now = _spin_until(w1)
    counters["w1"] = _snapshot_jt(jt)
    w1r = time.time_ns() - (time.monotonic_ns() - w1)
    if "w0" not in counters:
        counters["w0"] = counters["w1"]
    return (traffic.send_pool, due, np.array(sent, np.int64),
            traffic.send_outcome, w0, w1, w0r, w1r)


def _closed_loop(sock, traffic, mix, warm, seconds, jt, counters, count,
                 net_m, periodic):
    """Keeps `outstanding` transactions without a verdict (a damaged one
    has its verdict when sent), and never more than `socket_window`
    datagrams unread in the port's socket, so the kernel drops none."""
    limit = int(mix["outstanding"])
    sockwin = int(mix["socket_window"])
    npool = len(traffic.nsig)
    offs = traffic.offs.tolist()
    bad = (traffic.bad >= 0).tolist()
    mv = memoryview(traffic.wires)
    send = sock.send
    mono = time.monotonic_ns
    sent = []
    k = 0
    fails = 0
    rx0 = net_m.get("rx_pkt_cnt")
    t0 = mono()
    w0 = t0 + int(warm * 1e9)
    w1 = w0 + int(seconds * 1e9)
    w0r = 0
    in_window = False
    now = t0
    while now < w1:
        room = min(limit - (k - count.value - fails),
                   sockwin - (k - (net_m.get("rx_pkt_cnt") - rx0)))
        if room <= 0:
            now = mono()
            periodic(now)
            continue
        for _ in range(min(room, 256)):
            p = k % npool
            send(mv[offs[p]:offs[p + 1]])
            fails += bad[p]
            k += 1
        now = mono()
        sent.extend([now] * min(room, 256))
        if not in_window and now >= w0:
            in_window = True
            counters["w0"] = _snapshot_jt(jt)
            w0r = time.time_ns() - (mono() - w0)
        periodic(now)
    counters["w1"] = _snapshot_jt(jt)
    w1r = time.time_ns() - (mono() - w1)
    if "w0" not in counters:
        counters["w0"] = counters["w1"]
    send_pool = np.arange(k, dtype=np.int64) % npool
    send_t = np.array(sent, np.int64)
    outcome = np.where(traffic.bad[send_pool] >= 0, gen.FAIL,
                       gen.PASS).astype(np.int8)
    return send_pool, send_t, send_t, outcome, w0, w1, w0r, w1r
