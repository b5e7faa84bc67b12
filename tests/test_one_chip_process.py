"""One process per chip: the topology names one device-owning tile, every
other tile keeps JAX on the CPU, a topology with device work in a second
process is refused unless JAX is pinned to the CPU, and the persistent
compile cache follows JAX_COMPILATION_CACHE_DIR."""

import multiprocessing as mp
import os
import subprocess
import sys

import pytest

from firedancer_tpu.app import config as config_mod
from firedancer_tpu.disco import run as run_mod
from firedancer_tpu.disco import topo as topo_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(topology: str, **over) -> dict:
    cfg = config_mod.load(None, environ={})
    cfg["topology"] = topology
    cfg["development"]["source_count"] = 16
    for path, val in over.items():
        sect, key = path.split(".")
        cfg[sect][key] = val
    return cfg


def _fdtpu_with_shred(backend: str) -> dict:
    cfg = _cfg("fdtpu")
    cfg["consensus"]["genesis_path"] = "genesis.bin"  # read at tile init
    cfg["tiles"]["shred"]["sig_backend"] = backend
    return cfg


TWO_DEVICE_TILES = {
    "two verify tiles": (_cfg("verify-bench", **{"layout.verify_tile_count": 2}),
                         ["verify:0", "verify:1"]),
    "verify + device shred": (_fdtpu_with_shred("device"),
                              ["verify:0", "shred"]),
    "verify + poh_dev": (_cfg("leader-bench"), ["verify:0", "poh_dev"]),
}


@pytest.mark.parametrize("case", sorted(TWO_DEVICE_TILES))
def test_second_device_process_refused_off_cpu(case, monkeypatch):
    cfg, tiles = TWO_DEVICE_TILES[case]
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(ValueError) as ei:
        config_mod.build_topology(cfg)
    for t in tiles:
        assert t in str(ei.value)
    # pinned to the CPU, every tile gets its own CPU backend: allowed
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    spec = config_mod.build_topology(cfg)
    assert sorted(topo_mod.device_tiles(spec)) == sorted(tiles)


def test_one_device_tile_is_the_owner(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    spec = config_mod.build_topology(_cfg("verify-bench"))
    assert topo_mod.device_owner(spec) == "verify:0"
    spec = config_mod.build_topology(_fdtpu_with_shred("host"))
    assert topo_mod.device_owner(spec) == "verify:0"


def _pinned_child(spec, name, q):
    run_mod.pin_device(spec, name)
    import jax
    q.put((os.environ.get("JAX_PLATFORMS"), jax.config.jax_platforms,
           jax.devices()[0].platform,
           os.environ.get("FDTPU_XLA_CACHE_READONLY")))


def test_non_owner_tile_comes_up_off_the_chip(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("FDTPU_XLA_CACHE_READONLY", raising=False)
    spec = config_mod.build_topology(_cfg("verify-bench"))
    # the owner keeps whatever platform the operator chose, and writes
    # the compile cache
    run_mod.pin_device(spec, "verify:0")
    assert "JAX_PLATFORMS" not in os.environ
    assert "FDTPU_XLA_CACHE_READONLY" not in os.environ
    # a non-owner tile process, spawned the way disco.run spawns tiles
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_pinned_child, args=(spec, "source", q))
    p.start()
    try:
        got = q.get(timeout=120)
    finally:
        p.join(30)
    assert not p.is_alive() and p.exitcode == 0
    assert got == ("cpu", "cpu", "cpu", "1")


def test_xla_cache_follows_jax_compilation_cache_dir(tmp_path):
    code = ("from firedancer_tpu.utils import xla_cache; xla_cache.enable(); "
            "import jax; print(xla_cache.cache_dir()); "
            "print(jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(tmp_path)] * 2
    del env["JAX_COMPILATION_CACHE_DIR"]
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [os.path.join(REPO, ".xla_cache")] * 2
