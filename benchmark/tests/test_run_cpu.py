"""A whole run on the CPU at a tiny size: the harness's look for a chip
skipped, everything else as on the chip (topology boot, UDP traffic,
observer, comparison).  A sound program is correct; with the timed path
broken underneath by the program's own fault injection it is not; and
with the look for a chip on, the run stops at the device check.

Minutes of CPU compile per run; not part of the repository's tier-1 run:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_run_cpu.py
"""

import copy
import json
import os
import time
from pathlib import Path

import pytest

from benchmark import cells, harness
from benchmark import run as brun

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu",
    reason="drives a whole topology on the CPU backend")

SEED = 2**31 + 77
FIREHOSE = "ingest_packed_1chip.transfer_firehose"
OPEN = "open_legacy"
FIXTURES = Path(__file__).parent / "fixtures"


def open_legacy_cell() -> cells.Cell:
    """The open loop on the legacy per-transaction path with its length
    ladder: the cell's configuration with the packed path off, and a test
    mix of many shapes (fixtures/open_mix.json)."""
    fh = cells.resolve(FIREHOSE)
    config = copy.deepcopy(fh.config)
    config["topology"]["quic"]["packed_publish"] = 0
    config["topology"]["ingest"]["egress_packed"] = 0
    mix = json.loads((FIXTURES / "open_mix.json").read_text())
    e2e = [{"name": n, "unit": u} for n, u in (
        ("setup_s", "s"), ("sigs_per_s", "sigs/s"),
        ("p50_verdict_ms", "ms"), ("p99_verdict_ms", "ms"))]
    return cells.Cell(OPEN, 1, config, mix, e2e, [])


def tiny(name: str, verify_extra: dict | None = None):
    extra = {"tiles": {"verify": {"batch": 32, "tcache_depth": 4096},
                       "dedup": {"tcache_depth": 4096}}}
    if name == OPEN:
        cell = open_legacy_cell()
        extra["tiles"]["verify"]["buckets"] = [[32, 256], [16, 1232]]
    else:
        cell = cells.resolve(name)
        cell.mix = dict(cell.mix, outstanding=128, socket_window=64)
    extra["tiles"]["verify"].update(verify_extra or {})
    return cell, extra


def measure(name, verify_extra=None, require_tpu=False):
    cell, extra = tiny(name, verify_extra)
    return brun.measure(cell, SEED, 3.0, False, time.monotonic(),
                        require_tpu=require_tpu, topo_extra=extra)[0]


@pytest.mark.parametrize("name,metrics", [
    (FIREHOSE, {"setup_s", "sigs_per_s"}),
    (OPEN, {"setup_s", "sigs_per_s", "p50_verdict_ms", "p99_verdict_ms"}),
])
def test_sound_run_is_correct(name, metrics):
    res = measure(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 100 and res["failed"] == 0
    assert set(res["metrics"]) == metrics


@pytest.mark.parametrize("name,fault", [
    # half of the batch left out: the verify tile drops half its input
    (OPEN, {"burst": False, "faults": {"drop_frag_p": 0.5, "seed": 1}}),
    # an answer altered where it is produced: packed rows damaged in the
    # verify tile's input view
    (FIREHOSE, {"faults": {"corrupt_payload_p": 1.0, "seed": 1}}),
])
def test_broken_path_is_not_correct(name, fault):
    res = measure(name, fault)
    assert not res["correct"]
    assert res["checks"]["missing"]["value"] > 0


def test_device_check_stops_a_cpu_run():
    with pytest.raises(harness.NoDevice):
        measure(FIREHOSE, require_tpu=True)
