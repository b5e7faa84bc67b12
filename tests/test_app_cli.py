"""App layer: TOML config layering, env overlay, topology materialization,
CLI actions (ref behaviors: src/app/fdctl config.c + main1.c action table)."""

import json

import pytest

from firedancer_tpu.app import config as config_mod
from firedancer_tpu.app import fdtpuctl


def test_default_config_builds_ingest_topology():
    cfg = config_mod.load()
    spec = config_mod.build_topology(cfg)
    kinds = {t.kind for t in spec.tiles}
    assert {"net", "quic", "verify", "dedup", "pack", "sink"} <= kinds
    assert "bank" not in kinds  # no genesis configured -> ingest-only


def test_config_overlay_and_env(tmp_path):
    p = tmp_path / "user.toml"
    p.write_text("""
[layout]
verify_tile_count = 3
[tiles.verify]
batch = 128
""")
    cfg = config_mod.load(str(p), environ={
        "FDTPU_LAYOUT_VERIFY_TILE_COUNT": "4",
        "FDTPU_TILES_VERIFY_MSG_MAXLEN": "512",
    })
    assert cfg["layout"]["verify_tile_count"] == 4      # env wins
    assert cfg["tiles"]["verify"]["batch"] == 128       # file wins
    assert cfg["tiles"]["verify"]["msg_maxlen"] == 512  # env nested
    spec = config_mod.build_topology(cfg)
    verifies = [t for t in spec.tiles if t.kind == "verify"]
    assert len(verifies) == 4
    assert verifies[1].cfg["round_robin_idx"] == 1
    assert verifies[1].cfg["batch"] == 128


@pytest.mark.parametrize("mode", ["antipa", "rlc"])
def test_removed_verify_mode_rejected_at_load(tmp_path, mode):
    p = tmp_path / "user.toml"
    p.write_text(f'[verify]\nmode = "{mode}"\n')
    with pytest.raises(ValueError, match="removed"):
        config_mod.load(str(p), environ={})


def test_strict_verify_mode_and_cell_overlays_build(tmp_path):
    """[verify] mode = "strict" loads, and each benchmark cell's overlay
    (which names the mode, merged after load() as the harness does)
    still builds its topology; no builder copies the key into the verify
    tile's cfg."""
    import glob
    import os

    from benchmark import harness

    p = tmp_path / "user.toml"
    p.write_text('[verify]\nmode = "strict"\n')
    assert config_mod.load(str(p), environ={})["verify"]["mode"] == "strict"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = sorted(glob.glob(os.path.join(root, "benchmark", "configs",
                                          "*.json")))
    assert paths
    for path in paths:
        with open(path) as f:
            overlay = json.load(f)["topology"]
        spec = config_mod.build_topology(harness.topology_config(overlay))
        verifies = [t for t in spec.tiles if t.kind == "verify"]
        assert verifies and all("mode" not in t.cfg for t in verifies)


def test_full_topology_with_consensus(tmp_path):
    cfg = config_mod.load()
    cfg["consensus"]["genesis_path"] = str(tmp_path / "g.bin")
    cfg["consensus"]["identity_path"] = str(tmp_path / "id.json")
    spec = config_mod.build_topology(cfg)
    kinds = {t.kind for t in spec.tiles}
    assert {"net", "quic", "verify", "dedup", "pack", "bank", "poh",
            "shred", "sign", "store"} <= kinds


def test_keys_roundtrip_and_topo_print(tmp_path, capsys):
    kpath = str(tmp_path / "id.json")
    assert fdtpuctl.main(["keys", "new", kpath]) == 0
    pub_hex = capsys.readouterr().out.strip()
    assert len(bytes.fromhex(pub_hex)) == 32
    assert fdtpuctl.main(["keys", "pubkey", kpath]) == 0
    assert capsys.readouterr().out.strip() == pub_hex

    assert fdtpuctl.main(["topo"]) == 0
    out = capsys.readouterr().out
    assert "quic_verify" in out and "kind=verify" in out

    assert fdtpuctl.main(["version"]) == 0


def test_verify_bench_topology():
    cfg = config_mod.load()
    cfg["topology"] = "verify-bench"
    cfg["development"]["source_count"] = 100
    spec = config_mod.build_topology(cfg)
    kinds = [t.kind for t in spec.tiles]
    assert kinds.count("source") == 1 and "sink" in kinds


def test_mem_report(capsys):
    from firedancer_tpu.app import fdtpuctl

    assert fdtpuctl.main(["mem"]) == 0
    out = capsys.readouterr().out
    assert "TOTAL" in out and "mcache" in out


def test_tile_profiling_hook(tmp_path, monkeypatch):
    """FDTPU_PROFILE_DIR makes every tile dump a cProfile .pstats at exit
    (the fddev-flame hook, src/app/fddev/flame.c role)."""
    import os
    import pstats

    from firedancer_tpu.disco.run import TopoRun
    from firedancer_tpu.disco.topo import TopoBuilder

    prof_dir = str(tmp_path / "prof")
    monkeypatch.setenv("FDTPU_PROFILE_DIR", prof_dir)
    spec = (
        TopoBuilder(f"flame{os.getpid()}", wksp_mb=8)
        .link("src_sink", depth=64, mtu=1280)
        .tile("source", "source", outs=["src_sink"], count=8, keys=1)
        .tile("sink", "sink", ins=["src_sink"])
        .build()
    )
    import time
    with TopoRun(spec) as run:
        run.wait_ready(timeout=300)
        deadline = time.monotonic() + 60
        while (time.monotonic() < deadline
               and run.metrics("sink")["frag_cnt"] < 8):
            time.sleep(0.05)
        assert run.metrics("sink")["frag_cnt"] == 8
    # teardown flushed the profiles
    files = sorted(os.listdir(prof_dir))
    assert files == ["sink.pstats", "source.pstats"]
    st = pstats.Stats(os.path.join(prof_dir, "source.pstats"))
    assert st.total_calls > 0


def test_fdtpudbg_ps_and_stack(tmp_path):
    """fddbg analogue: list a running topology's tiles and trigger a
    non-disruptive faulthandler stack dump (the tile survives it)."""
    import os
    import time

    from firedancer_tpu.app.fdtpudbg import main as dbg_main
    from firedancer_tpu.disco.run import TopoRun
    from firedancer_tpu.disco.topo import TopoBuilder

    name = f"dbg{os.getpid()}"
    spec = (TopoBuilder(name, wksp_mb=4)
            .link("a_b", depth=16, mtu=256)
            .tile("src", "source", outs=["a_b"], count=0, keys=1)
            .tile("snk", "sink", ins=["a_b"])
            .build())
    with TopoRun(spec) as run:
        run.wait_ready(timeout=120)
        assert dbg_main(["ps", name]) == 0
        assert dbg_main(["stack", name]) == 0
        time.sleep(0.5)
        # non-disruptive: the tiles are still alive and flowing
        assert run.poll() is None
        assert run.metrics("snk")["frag_cnt"] >= 0
    assert dbg_main(["ps", f"definitely-missing-{name}"]) == 1


def test_netmux_blackhole_topology_registration():
    """VERDICT r4 #10: netmux fans N net tiles into one link; blackhole
    terminates the tail without reading payloads."""
    cfg = config_mod.load()
    cfg["layout"]["net_tile_count"] = 2
    cfg["development"]["sink_kind"] = "blackhole"
    spec = config_mod.build_topology(cfg).validate()
    kinds = [t.kind for t in spec.tiles]
    assert kinds.count("net") == 2
    assert "netmux" in kinds and "blackhole" in kinds
    mux = next(t for t in spec.tiles if t.kind == "netmux")
    assert len(mux.in_links) == 2 and len(mux.out_links) == 1


def test_netmux_blackhole_vtables():
    from firedancer_tpu.disco.tiles import BlackholeTile, NetmuxTile

    class Metrics:
        def __init__(self):
            self.c = {}

        def add(self, k, n=1):
            self.c[k] = self.c.get(k, 0) + n

    class Ctx:
        def __init__(self):
            self.metrics = Metrics()
            self.pub = []

        def publish(self, payload, sig=0):
            self.pub.append((bytes(payload), sig))

    ctx = Ctx()
    NetmuxTile().on_frag(ctx, 0, {"sig": 7}, b"payload")
    assert ctx.pub == [(b"payload", 7)]

    ctx2 = Ctx()
    bh = BlackholeTile()
    assert bh.before_frag(ctx2, 0, 5, 9) is True  # filter: never reads
    assert not ctx2.pub  # drop counted by the mux's in_filt_cnt slot


def test_monitor_follow_renders_dashboard(capsys):
    """--follow repaints in place: drive one frame against a freshly
    created (idle) topology."""
    import types

    from firedancer_tpu.disco import topo as topo_mod
    cfg = config_mod.load()
    cfg["name"] = "montest"
    spec = config_mod.build_topology(cfg)
    jt = topo_mod.create(spec)
    try:
        args = types.SimpleNamespace(interval=0.01, count=1, follow=True)
        rc = fdtpuctl._monitor_follow(spec, jt, args)
        assert rc == 0
        out = capsys.readouterr().out
        assert "fdtpu monitor" in out and "TILE" in out and "LINK" in out
        assert "verify:0" in out
    finally:
        jt.unlink()
