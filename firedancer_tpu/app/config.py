"""Layered TOML config -> topology materialization (ref: src/app/fdctl/
config.c:818-870 config_parse — compiled-in defaults <- --config file <-
env overrides; topo selection topos.c:6-12).

The compiled-in defaults live in DEFAULT_TOML below (the reference ships
src/app/fdctl/config/default.toml); a user file overlays it key-by-key;
FDTPU_* environment variables overlay scalars last (FDTPU_LAYOUT_VERIFY_
TILE_COUNT=4 sets [layout] verify_tile_count).
"""

import os
import tomllib

from ..disco.topo import InLink, TopoBuilder, TopoSpec

DEFAULT_TOML = """
name = "fdtpu"
topology = "fdtpu"          # fdtpu | verify-bench | leader-bench

[layout]
verify_tile_count = 1
bank_tile_count = 1
affinity = ""               # "" = no pinning | "auto" | "0,2,3" cpu list
                            # (tiles take cpus in topology order, wrapping)

[net]
listen_port = 9001
pps_per_source = 0          # >0: per-source-IP packet token bucket at the
                            # net tile (sheds -> rate_drop_cnt + shedding)
pps_burst = 0               # bucket depth (0 = 2x pps_per_source)

[quic]                      # DoS front-door knobs (threaded to the quic
                            # tiles / QuicConfig; see docs/guide.md)
max_conns = 4096            # global conn table cap (idle-LRU evict on full)
max_conns_per_peer = 32     # conns one source IP may hold (0 = unlimited)
retry = 0                   # 1: ALWAYS require stateless Retry tokens
retry_half_open_threshold = 64  # half-open conns before Retry turns
                            # mandatory for tokenless Initials (0 = off)
lru_evict_idle = 1.0        # idle secs before a conn is LRU-evictable
conn_txn_rate = 0.0         # per-conn completed-txn/s token bucket (0 = off)
conn_txn_burst = 32
conn_reasm_budget = 19712   # partial-stream bytes buffered per conn (16 MTU)
reasm_conn_budget = 0       # TpuReasm slot-bytes per conn (0 = off)
idle_timeout = 10.0
packed_publish = 0          # 1: stamp reassembled txns as packed dcache
                            # rows (zero-copy wire->device; 0 = legacy
                            # per-txn publish, bit-identical verdicts)
crypto_native = -1          # burst packet protection (aescrypt.cpp):
                            # -1 = auto (C engine if the .so builds, else
                            # the bit-identical NumPy fallback), 0 = force
                            # Python, 1 = require native.
                            # Env: FDTPU_QUIC_CRYPTO_NATIVE
initial_key_cache = 1024    # per-dcid Initial key-schedule LRU cap (a
                            # random-dcid flood holds at most this many
                            # expanded schedules; 0 = no caching)

[verify]
mode = "strict"             # strict | antipa (round 9: halved-scalar chain
                            # with in-kernel divstep — 128 doubles vs 256;
                            # default-off pending the driver A/B, and
                            # torsion-LAX on adversarial 8-torsion defects,
                            # see docs/guide.md).  Env: FDTPU_VERIFY_MODE

[ingest]
native_hostpath = 1         # 1: round-11 one-pass C submit/harvest kernel
                            # (hostpath.cpp) on packed dcache row views; 0 =
                            # NumPy fallback, bit-identical verdicts.
                            # Env: FDTPU_INGEST_NATIVE_HOSTPATH
egress_packed = 0           # 1: verify tiles publish ONE packed arena frag
                            # per harvest (u32 offs[k+1] | wires) instead of
                            # k per-txn frags; the dedup tile unpacks it.
                            # Requires a packed ingest topology
                            # ([quic] packed_publish or [development]
                            # packed_wire).  0 = legacy per-txn egress.

[tiles.verify]
batch = 64
msg_maxlen = 256
flush_age_ns = 2000000
tcache_depth = 65536
dp_shards = 1               # >1: shard each batch P("dp") over a device mesh

[latency]
enabled = 0                 # 1: dual-lane dispatch in verify tiles (frags
                            # with the sig priority bit take the small lane)
deadline_us = 2000          # close the low-latency batch when its oldest
                            # txn reaches this age, regardless of fill
shapes = [16, 64, 256]      # small-lane batch ladder, pre-warmed at boot
max_inflight = 2            # lat-lane inflight budget before spilling
spill_age_factor = 4.0      # spill when open-queue age > factor * deadline

[tiles.dedup]
tcache_depth = 1048576

[tiles.pack]
max_txn_per_microblock = 31

[tiles.bank]
slot_txn_max = 1024
slot_ns = 400000000

[tiles.poh]
hashes_per_tick = 64
ticks_per_slot = 64

[leader]                    # leader lane: pack -> device PoH (round 14;
                            # leader-bench topology + the fdtpu leader
                            # tiles; see docs/guide.md "[leader] lane")
hashes_per_tick = 16
ticks_per_slot = 8
spec_spans = 3              # concurrent engine span lanes: 1 chain lane +
                            # (spec_spans - 1) emitted-entry re-check lanes
poh_spec_ticks = 4          # PoH speculation depth: ticks pre-hashed per
                            # window dispatch (a mixin splices from the
                            # saved insertion point and invalidates the
                            # rest of the window)
mb_per_tick = 8             # mixin steps per tick (capped at
                            # hashes_per_tick - 1; excess microblocks defer)
pack_shards = 1             # leader_pack tiles, partitioned by fee-payer
                            # writable account; > 1 adds a leader_merge
                            # tile enforcing the global block budgets
native_pack = -1            # pack schedule hot loop: -1 = auto (native if
                            # the .so builds, else the bit-identical
                            # Python fallback), 0 = force Python, 1 =
                            # require native
mixin_txn_max = 32          # mixin merkle-tree pad width (txns/microblock)
max_txn_per_microblock = 31
max_pending = 4096          # pack heap cap (0 = unbounded; simple votes
                            # bypass — the reserved vote lane)
block_us = 400000           # end_block cadence (block budget reset)
unroll = 8                  # inner sha256 scan unroll factor (XLA fusion)
capture_path = ""           # sink capture file (sig|len|payload per frag)
                            # for offline chain re-verification; "" = off

[tiles.shred]
shred_version = 1
fec_data_cnt = 32
sig_batch = 32              # turbine-ingress batched leader-sig admission:
                            # shreds per merkle-walk + sigverify dispatch
sig_flush_age_us = 2000     # partial-batch deadline (age-or-size flush)
sig_backend = "device"      # "device" = batched graphs; "host" = per-shred
                            # python-int verify (control-plane rates)

[tiles.shred_recover]
fec_data_cnt = 32           # k_max: data shreds per set the engine packs
fec_code_cnt = 32           # parity bound; n_max = data + code
batch_sets = 8              # FEC sets per fused recover dispatch
flush_age_us = 5000         # partial-batch deadline for queued sets
nbuf = 2                    # rotating recover blobs (>= 2 to overlap)

[tiles.metric]
prometheus_port = 0         # 0 = disabled

[observability]
http_port = 0               # 0 = no supervisor /metrics + /healthz endpoint
flight_dir = ""             # "" = flight recorder off; else postmortem
                            # bundle dir (crash/degrade/respawn/SIGUSR2)
flight_max_bundles = 16     # oldest-bundle rotation bound on flight_dir
                            # (a crash loop can't fill the disk); evictions
                            # counted in fdtpu_flightrec_evict_cnt
slo_target_ms = 2.0         # e2e p99 latency target the stage budgets
                            # and /healthz slo field grade against

[autotune]                  # closed-loop tuner (disco/autotune.py): turns
                            # attribution verdicts + SLO burn into bounded
                            # knob moves.  WARNING: with enabled = 1 the
                            # loop owns its knob surface — hand-edits to
                            # [latency]/[tiles.verify]/rate knobs only set
                            # the BASELINE it relaxes back toward.
enabled = 0                 # default-off: zero overhead, bit-identical
                            # behavior (same invariant as faultinject)
period_s = 2.0              # control period (one sense + at most one move)
burn_hi = 0.35              # act when SLO burn rate >= this (hysteresis hi)
burn_lo = 0.10              # healthy below this (hysteresis lo)
cooldown_periods = 3        # periods a fired rule stays ineligible
relax_after = 10            # healthy periods before stepping a displaced
                            # knob back toward its boot baseline
quarantine_periods = 64     # rule lockout after a do-no-harm revert
respawn_after = 0           # >0: last resort — this many consecutive
                            # burn_hi periods respawns verify with the
                            # dispatch-ahead window at its hi clamp
poison = ""                 # test hook: invert the named rule's step
                            # direction (the chaos gate proves do-no-harm
                            # catches and reverts it)

[autotune.bounds]           # optional per-knob [lo, hi] or [lo, hi, step]
                            # overrides of disco/autotune.py KNOB_SPECS
                            # (knob names are globally unique, e.g.
                            # deadline_us = [500, 10000, 0.25])

[supervision]
restart_policy = "fail_fast"  # fail_fast (ref run.c:279) | respawn
max_restarts = 5              # per-tile respawn budget
backoff_initial_s = 0.25      # exponential backoff: initial delay,
backoff_max_s = 8.0           # cap, and +/- jitter fraction (jitter is
backoff_jitter = 0.2          # deterministic per (tile, attempt))
boot_grace_s = 300.0          # no staleness checks while a tile boots
heartbeat_stale_s = 60.0      # default heartbeat staleness -> tile failed
device_fail_threshold = 3     # consecutive dispatch failures -> CPU fallback
device_retry = 1              # bounded retries per device dispatch
device_deadline_s = 30.0      # verdict materialization deadline
device_reprobe_s = 5.0        # degraded-mode device re-probe interval
drain_timeout_s = 0.0         # >0: graceful drain budget (rolling restarts,
                              # SIGTERM/SIGINT topology drain).  A tile that
                              # is not DRAINED within the budget falls back
                              # to crash-respawn semantics + flight bundle.
                              # 0 (default): drain never engages — behavior
                              # bit-identical to a world without it.
drain_manifest_dir = ""       # where draining tiles persist their cursor
                              # manifests ("" = skip; $FDTPU_DRAIN_DIR also
                              # works per-process)

[supervision.heartbeat_stale] # per tile KIND overrides (seconds)
verify = 120.0                # uncached device dispatches stall longer

[consensus]
identity_path = ""
genesis_path = ""

[fleet]                     # multi-host fleet layer (disco/fleet.py).
hosts = 1                   # 1 = single-host mode: fleet layer fully inert
vnodes = 64                 # ring points per host (waltz SteerRing)
shard_bits = 4              # tcache shards = 2^bits (sig-prefix sharding)
digest_period_s = 0.5       # sig-digest gossip publish cadence per host
digest_chunk = 512          # max tags per gossip digest chunk
failover_timeout_s = 15.0   # host silent past this -> declared lost
gossip_port = 0             # control-ring UDP base port (0 = ephemeral)
host_boot_timeout_s = 120.0 # per-host topology wait_ready bound

[development]
source_count = 0            # >0: synthetic txn source instead of net ingest
source_burst_n = 0          # >0: numpy burst firehose (txns/loop; see SourceTile)
packed_wire = 0             # 1: dcache frags ARE device-blob rows (zero-copy
                            # wire->device path, verify-bench topology only)
burst_splits = 2            # packed frags emitted per source loop (round-robin
                            # deal across verify tiles)
lat_every = 0               # >0: tag every Nth synthetic txn latency-class
                            # (sets the sig priority bit; see [latency])
bench_seed = 42
"""


def _deep_merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out


def _env_overlay(cfg: dict, environ=os.environ) -> dict:
    """FDTPU_SECTION_KEY=value overrides; ints parsed when they look like
    ints (the reference parses env as the final layer, config.c)."""
    for name, val in environ.items():
        if not name.startswith("FDTPU_"):
            continue
        path = name[6:].lower().split("_", 1)
        cur = cfg
        # walk into the deepest section that matches; remaining underscore
        # words form the key (sections never contain underscores)
        if len(path) == 1:
            key = path[0]
        else:
            sect, key = path
            if sect in cur and isinstance(cur[sect], dict):
                cur = cur[sect]
                # tiles.verify style: one more level
                head = key.split("_", 1)
                if (len(head) == 2 and head[0] in cur
                        and isinstance(cur[head[0]], dict)):
                    cur = cur[head[0]]
                    key = head[1]
            else:
                key = name[6:].lower()
        try:
            cur[key] = int(val)
        except ValueError:
            cur[key] = val
    return cfg


# Sections where an unknown key is an ERROR, not a silent no-op: these
# all carry tuning knobs, and a typo'd knob (deadline_uss) that no-ops is
# the worst possible failure mode for an autotuned topology.  The valid
# key set IS the DEFAULT_TOML schema; listed sub-tables are exempt
# (heartbeat_stale keys are tile kinds, bounds keys are knob names —
# the latter validated against the autotune KNOB_SPECS registry).
_STRICT_SECTIONS = ("latency", "verify", "supervision", "observability",
                    "autotune", "leader", "fleet")
_STRICT_SUBTABLES = {"supervision": ("heartbeat_stale",),
                     "autotune": ("bounds",)}


def _validate_strict(cfg: dict):
    import difflib
    schema = tomllib.loads(DEFAULT_TOML)
    for sect in _STRICT_SECTIONS:
        got = cfg.get(sect)
        if not isinstance(got, dict):
            continue
        valid = set(schema[sect]) | set(_STRICT_SUBTABLES.get(sect, ()))
        for key in got:
            if key in valid:
                continue
            near = difflib.get_close_matches(key, sorted(valid), n=1)
            hint = f" (did you mean {near[0]!r}?)" if near else ""
            raise ValueError(
                f"unknown key {key!r} in [{sect}]{hint}; valid keys: "
                + ", ".join(sorted(valid)))
    bounds = (cfg.get("autotune") or {}).get("bounds") or {}
    if bounds:
        from ..disco.autotune import KNOB_SPECS
        for knob, b in bounds.items():
            if knob not in KNOB_SPECS:
                near = difflib.get_close_matches(
                    knob, sorted(KNOB_SPECS), n=1)
                hint = f" (did you mean {near[0]!r}?)" if near else ""
                raise ValueError(
                    f"unknown knob {knob!r} in [autotune.bounds]{hint}")
            if (not isinstance(b, (list, tuple)) or len(b) not in (2, 3)
                    or not all(isinstance(x, (int, float)) for x in b)):
                raise ValueError(
                    f"[autotune.bounds] {knob} must be [lo, hi] or "
                    f"[lo, hi, step], got {b!r}")


def load(path: str | None = None, environ=os.environ) -> dict:
    cfg = tomllib.loads(DEFAULT_TOML)
    if path:
        with open(path, "rb") as f:
            cfg = _deep_merge(cfg, tomllib.load(f))
    cfg = _env_overlay(cfg, environ)
    _validate_strict(cfg)
    return cfg


def build_topology(cfg: dict) -> TopoSpec:
    """Materialize the configured topology (the fd_topo_frankendancer /
    fd_topo_firedancer analogues, src/app/fdctl/run/topos/)."""
    name = cfg.get("topology", "fdtpu")
    if name == "fdtpu":
        spec = _topo_fdtpu(cfg)
    elif name == "verify-bench":
        spec = _topo_verify_bench(cfg)
    elif name == "leader-bench":
        spec = _topo_leader_bench(cfg)
    else:
        raise ValueError(f"unknown topology {name!r}")
    from ..disco.topo import assign_affinity
    return assign_affinity(spec, str(cfg["layout"].get("affinity", "")))


def _topo_fdtpu(cfg: dict) -> TopoSpec:
    """The full single-host validator graph:

        net -> quic -> verify[v] -> dedup -> pack -> bank -> poh
           -> shred (keyguard-signed) -> store        (+ metric tile)

    verify tiles are round-robin data parallel (fd_verify.c:36-47); with
    [development] source_count > 0 a synthetic source replaces net+quic.
    """
    lay = cfg["layout"]
    nverify = int(lay["verify_tile_count"])
    t = cfg["tiles"]
    qcfg = dict(cfg.get("quic") or {})
    dev_count = int(cfg["development"]["source_count"])
    # [quic] packed_publish: the quic tile stamps reassembled txns as
    # packed device-blob rows (round-8 layout) — same link/vcfg shape as
    # the verify-bench packed_wire topology
    packed = bool(int(qcfg.get("packed_publish", 0))) and not dev_count
    b = TopoBuilder(cfg.get("name", "fdtpu"),
                    wksp_mb=128 if packed else 64)

    # degraded-mode thresholds + fault plans ride in the verify tile cfg
    # (the [supervision] respawn half is supervisor-side only); the
    # [verify] mode knob (strict|antipa, FDTPU_VERIFY_MODE) rides along
    # so every verify tile builds the same device graph
    vcfg = dict(t["verify"])
    vcfg["mode"] = str(cfg.get("verify", {}).get("mode", "strict"))
    ing = dict(cfg.get("ingest") or {})
    vcfg["native_hostpath"] = int(ing.get("native_hostpath", 1))
    # packed arena egress rides the packed ingest path only: one frag per
    # harvest, so the verify_dedup link must fit a whole arena (k wires of
    # up to 65+ml bytes each plus the u32 offsets table)
    egress_packed = bool(int(ing.get("egress_packed", 0))) and packed
    if egress_packed:
        vcfg["egress_packed"] = 1
    if dev_count:
        b.link("quic_verify", depth=256, mtu=1280)
        b.tile("source", "source", outs=["quic_verify"], count=dev_count,
               seed=int(cfg["development"]["bench_seed"]),
               burst_n=int(cfg["development"].get("source_burst_n", 0)),
               lat_every=int(cfg["development"].get("lat_every", 0)))
    else:
        b.link("net_quic", depth=256, mtu=2048)
        if packed:
            from ..tango.ring import PACKED_ROW_EXTRA, packed_row_ml
            batch = int(vcfg.get("batch", 64))
            ml = packed_row_ml(int(vcfg.get("msg_maxlen", 256)))
            vcfg["packed_wire"] = 1
            vcfg["buckets"] = [[batch, ml]]
            qcfg.update(packed_rows=batch, packed_ml=ml)
            b.link("quic_verify", depth=16,
                   mtu=batch * (ml + PACKED_ROW_EXTRA))
        else:
            b.link("quic_verify", depth=256, mtu=1280)
        pps = {"pps_per_source": int(cfg["net"].get("pps_per_source", 0)),
               "pps_burst": int(cfg["net"].get("pps_burst", 0))}
        nnet = int(lay.get("net_tile_count", 1))
        if nnet > 1:
            # N net tiles fan into one netmux (ref fd_netmux.c's role:
            # consumers join ONE mcache no matter how many ingress tiles).
            # Kernel-socket backends can't share a port, so tile i binds
            # listen_port+i; the XDP tier round-robins one port instead.
            for i in range(nnet):
                b.link(f"net_mux:{i}", depth=256, mtu=2048)
                b.tile(f"net:{i}", "net", outs=[f"net_mux:{i}"],
                       ports={int(cfg["net"]["listen_port"]) + i:
                              f"net_mux:{i}"}, **pps)
            b.tile("netmux", "netmux",
                   ins=[f"net_mux:{i}" for i in range(nnet)],
                   outs=["net_quic"])
        else:
            b.tile("net", "net", outs=["net_quic"],
                   ports={int(cfg["net"]["listen_port"]): "net_quic"},
                   **pps)
        b.tile("quic", "quic", ins=["net_quic"], outs=["quic_verify"],
               **qcfg)

    vcfg.setdefault("supervision", dict(cfg.get("supervision") or {}))
    vcfg.setdefault("latency", dict(cfg.get("latency") or {}))
    if egress_packed:
        from ..tango.ring import packed_row_ml
        batch = int(vcfg.get("batch", 64))
        ml = packed_row_ml(int(vcfg.get("msg_maxlen", 256)))
        vd_depth, vd_mtu = 16, batch * (65 + ml) + 4 * (batch + 1)
    else:
        vd_depth, vd_mtu = 256, 1280
    for v in range(nverify):
        b.link(f"verify_dedup:{v}", depth=vd_depth, mtu=vd_mtu)
        b.tile(f"verify:{v}", "verify", ins=["quic_verify"],
               outs=[f"verify_dedup:{v}"],
               round_robin_cnt=nverify, round_robin_idx=v,
               **vcfg)
    b.link("dedup_pack", depth=256, mtu=1280)
    b.tile("dedup", "dedup",
           ins=[f"verify_dedup:{v}" for v in range(nverify)],
           outs=["dedup_pack"], packed_egress=int(egress_packed),
           **t["dedup"])
    b.link("pack_bank", depth=256, mtu=1280)
    b.tile("pack", "pack", ins=["dedup_pack"], outs=["pack_bank"],
           max_txn=t["pack"]["max_txn_per_microblock"])

    gpath = cfg["consensus"]["genesis_path"]
    kpath = cfg["consensus"]["identity_path"]
    if gpath:
        b.link("bank_poh", depth=256, mtu=1280)
        b.link("poh_shred", depth=256, mtu=2048)
        b.link("shred_sign", depth=16, mtu=128)
        b.link("sign_shred", depth=16, mtu=128)
        b.link("shred_store", depth=512, mtu=1280)
        b.tile("bank", "bank", ins=["pack_bank"], outs=["bank_poh"],
               genesis_path=gpath, **t["bank"])
        b.tile("poh", "poh", ins=["bank_poh"], outs=["poh_shred"],
               **t["poh"])
        b.tile("shred", "shred", ins=["poh_shred"],
               outs=["shred_sign", "shred_store"], **t["shred"])
        b.tile("sign", "sign", ins=["shred_sign"], outs=["sign_shred"],
               key_path=kpath)
        b.tile("store", "store", ins=["shred_store"])
    else:
        # ingest-only slice (Frankendancer-without-Agave shape): count txns
        # (sink) or drop at metadata rate without reading payloads
        # (blackhole, ref fd_blackhole.c)
        b.tile("sink", cfg["development"].get("sink_kind", "sink"),
               ins=["pack_bank"])
    if int(t["metric"]["prometheus_port"]):
        b.tile("metric", "metric", ins=(),
               port=int(t["metric"]["prometheus_port"]))
    return b.build()


def _topo_verify_bench(cfg: dict) -> TopoSpec:
    """source -> verify[v] -> dedup -> sink: the synthetic sigverify load
    harness (the verify_synth_load.c / `fddev bench` analogue)."""
    lay = cfg["layout"]
    nverify = int(lay["verify_tile_count"])
    t = cfg["tiles"]
    dev = cfg["development"]
    vcfg = dict(t["verify"])
    vcfg["mode"] = str(cfg.get("verify", {}).get("mode", "strict"))
    packed = int(dev.get("packed_wire", 0))
    ing = dict(cfg.get("ingest") or {})
    vcfg["native_hostpath"] = int(ing.get("native_hostpath", 1))
    egress_packed = bool(int(ing.get("egress_packed", 0))) and bool(packed)
    if egress_packed:
        vcfg["egress_packed"] = 1
    b = TopoBuilder(cfg.get("name", "fdtpu") + "-bench",
                    wksp_mb=128 if packed else 64)
    if packed:
        # zero-copy wire->device: the src_verify dcache chunk layout IS
        # the PackedIngest device-blob layout.  One frag = one packed
        # burst of `batch` rows at a chunk-aligned stride; meta.sz
        # carries the row count (u16 can't hold the byte size).  Small
        # depth — frags are few and huge, and the reader pins them until
        # verdicts land (mux credits_held).
        from ..tango.ring import PACKED_ROW_EXTRA, packed_row_ml
        batch = int(vcfg.get("batch", 64))
        ml = packed_row_ml(int(vcfg.get("msg_maxlen", 256)))
        stride = ml + PACKED_ROW_EXTRA
        vcfg["packed_wire"] = 1
        vcfg["buckets"] = [[batch, ml]]
        b.link("src_verify", depth=16, mtu=batch * stride)
        b.tile("source", "source", outs=["src_verify"],
               count=int(dev["source_count"]),
               seed=int(dev["bench_seed"]),
               packed_rows=batch, packed_ml=ml,
               burst_splits=int(dev.get("burst_splits", 2)))
    else:
        b.link("src_verify", depth=4096, mtu=1280)
        # source_extra: fleet harness passthrough (adopt_streams,
        # rate_ns, ... — disco/fleet.py host topologies)
        b.tile("source", "source", outs=["src_verify"],
               count=int(dev["source_count"]),
               seed=int(dev["bench_seed"]),
               burst_n=int(dev.get("source_burst_n", 0)),
               lat_every=int(dev.get("lat_every", 0)),
               **dict(dev.get("source_extra") or {}))
    vcfg.setdefault("supervision", dict(cfg.get("supervision") or {}))
    vcfg.setdefault("latency", dict(cfg.get("latency") or {}))
    if egress_packed:
        vd_depth = 16
        vd_mtu = int(vcfg["buckets"][0][0]) * (65 + int(vcfg["buckets"][0][1])) \
            + 4 * (int(vcfg["buckets"][0][0]) + 1)
    else:
        vd_depth, vd_mtu = 256, 1280
    for v in range(nverify):
        b.link(f"verify_dedup:{v}", depth=vd_depth, mtu=vd_mtu)
        b.tile(f"verify:{v}", "verify", ins=["src_verify"],
               outs=[f"verify_dedup:{v}"],
               round_robin_cnt=nverify, round_robin_idx=v, **vcfg)
    b.link("dedup_sink", depth=256, mtu=1280)
    b.tile("dedup", "dedup",
           ins=[f"verify_dedup:{v}" for v in range(nverify)],
           outs=["dedup_sink"], packed_egress=int(egress_packed),
           **t["dedup"])
    b.tile("sink", "sink", ins=["dedup_sink"],
           **dict(t.get("sink") or {}))
    if int(t["metric"]["prometheus_port"]):
        b.tile("metric", "metric", ins=(),
               port=int(t["metric"]["prometheus_port"]))
    return b.build()


def _topo_leader_bench(cfg: dict) -> TopoSpec:
    """source -> verify[v] -> leader_pack -> poh_dev -> sink: the leader
    write-side harness (round 14) — verified txns feed the fee-priority
    pack scheduler, whose microblocks mix into the device PoH chain; the
    sink collects serialized entries (a test/chaos harness re-verifies
    them through ballet.poh.verify_entries)."""
    lay = cfg["layout"]
    nverify = int(lay["verify_tile_count"])
    t = cfg["tiles"]
    dev = cfg["development"]
    ld = dict(cfg.get("leader") or {})
    vcfg = dict(t["verify"])
    vcfg["mode"] = str(cfg.get("verify", {}).get("mode", "strict"))
    packed = int(dev.get("packed_wire", 0))
    ing = dict(cfg.get("ingest") or {})
    vcfg["native_hostpath"] = int(ing.get("native_hostpath", 1))
    egress_packed = bool(int(ing.get("egress_packed", 0))) and bool(packed)
    if egress_packed:
        vcfg["egress_packed"] = 1
    b = TopoBuilder(cfg.get("name", "fdtpu") + "-leader",
                    wksp_mb=128 if packed else 64)
    if packed:
        from ..tango.ring import PACKED_ROW_EXTRA, packed_row_ml
        batch = int(vcfg.get("batch", 64))
        ml = packed_row_ml(int(vcfg.get("msg_maxlen", 256)))
        stride = ml + PACKED_ROW_EXTRA
        vcfg["packed_wire"] = 1
        vcfg["buckets"] = [[batch, ml]]
        b.link("src_verify", depth=16, mtu=batch * stride)
        b.tile("source", "source", outs=["src_verify"],
               count=int(dev["source_count"]),
               seed=int(dev["bench_seed"]),
               packed_rows=batch, packed_ml=ml,
               burst_splits=int(dev.get("burst_splits", 2)))
    else:
        b.link("src_verify", depth=4096, mtu=1280)
        b.tile("source", "source", outs=["src_verify"],
               count=int(dev["source_count"]),
               seed=int(dev["bench_seed"]),
               burst_n=int(dev.get("source_burst_n", 0)),
               lat_every=int(dev.get("lat_every", 0)))
    vcfg.setdefault("supervision", dict(cfg.get("supervision") or {}))
    vcfg.setdefault("latency", dict(cfg.get("latency") or {}))
    if egress_packed:
        vd_depth = 16
        vd_mtu = int(vcfg["buckets"][0][0]) * (65 + int(vcfg["buckets"][0][1])) \
            + 4 * (int(vcfg["buckets"][0][0]) + 1)
    else:
        vd_depth, vd_mtu = 256, 1280
    for v in range(nverify):
        b.link(f"verify_pack:{v}", depth=vd_depth, mtu=vd_mtu)
        b.tile(f"verify:{v}", "verify", ins=["src_verify"],
               outs=[f"verify_pack:{v}"],
               round_robin_cnt=nverify, round_robin_idx=v, **vcfg)
    mtxn = int(ld.get("max_txn_per_microblock", 31))
    mb_mtu = 4 + mtxn * (4 + 1280)          # serialize_txn_batch wire
    b.link("pack_poh", depth=256, mtu=mb_mtu)
    shards = max(1, int(ld.get("pack_shards", 1)))
    pack_kw = dict(packed_egress=int(egress_packed), max_txn=mtxn,
                   max_pending=int(ld.get("max_pending", 4096)),
                   block_us=int(ld.get("block_us", 400_000)),
                   native_pack=int(ld.get("native_pack", -1)))
    if shards == 1:
        b.tile("leader_pack", "leader_pack",
               ins=[f"verify_pack:{v}" for v in range(nverify)],
               outs=["pack_poh"], **pack_kw)
    else:
        # sharded pack: every shard sees every verified txn and keeps
        # only its fee-payer partition; leader_merge interleaves the
        # per-shard microblocks and re-enforces the GLOBAL block budgets
        # (a txn payload caps writable accounts at ~38, 16 B per merge
        # item — size the shard->merge links for the worst case)
        merge_mtu = mb_mtu + 24 + 40 * mtxn * 16  # MERGE_HDR + items
        for s in range(shards):
            b.link(f"pack_merge:{s}", depth=64, mtu=merge_mtu)
            b.tile(f"leader_pack:{s}", "leader_pack",
                   ins=[f"verify_pack:{v}" for v in range(nverify)],
                   outs=[f"pack_merge:{s}"],
                   shard_cnt=shards, shard_idx=s, **pack_kw)
        b.tile("leader_merge", "leader_merge",
               ins=[f"pack_merge:{s}" for s in range(shards)],
               outs=["pack_poh"],
               block_us=int(ld.get("block_us", 400_000)))
    mixin_max = int(ld.get("mixin_txn_max", 32))
    entry_mtu = 48 + mixin_max * (4 + 1280)  # Entry.serialize wire
    b.link("poh_sink", depth=512, mtu=entry_mtu)
    b.tile("poh_dev", "poh_dev", ins=["pack_poh"], outs=["poh_sink"],
           hashes_per_tick=int(ld.get("hashes_per_tick", 16)),
           ticks_per_slot=int(ld.get("ticks_per_slot", 8)),
           spec_spans=int(ld.get("spec_spans", 3)),
           spec_ticks=int(ld.get("poh_spec_ticks", 4)),
           mb_per_tick=int(ld.get("mb_per_tick", 8)),
           mixin_txn_max=mixin_max,
           unroll=int(ld.get("unroll", 8)))
    b.tile("sink", "sink", ins=["poh_sink"],
           capture_path=str(ld.get("capture_path", "")))
    if int(t["metric"]["prometheus_port"]):
        b.tile("metric", "metric", ins=(),
               port=int(t["metric"]["prometheus_port"]))
    return b.build()
