"""Wave-parallel block execution (VERDICT r2 missing #6; ref
fd_runtime_block_eval_tpool, src/flamenco/runtime/fd_runtime.h:194):
account-lock wave planning, process-pool execution, and the bit-exact
bank-hash equivalence with serial replay that lthash commutativity
guarantees, with a fixed worker count and with one worker per core.
Replay speed is not asserted here: a shared test host cannot time it."""

import os

import pytest

from firedancer_tpu.ballet import entry as entry_lib
from firedancer_tpu.ballet import txn as txn_lib
from firedancer_tpu.flamenco import genesis as gen_mod
from firedancer_tpu.flamenco import replay as replay_mod
from firedancer_tpu.flamenco import system_program as sysprog
from firedancer_tpu.flamenco.parallel_exec import plan_waves
from firedancer_tpu.flamenco.runtime import Runtime
from firedancer_tpu.flamenco.types import SYSTEM_PROGRAM_ID, Account
from firedancer_tpu.ops import ed25519 as ed


def _keypair(i):
    seed = i.to_bytes(32, "little")
    return seed, ed.keypair_from_seed(seed)[0]


def _transfer(src, dest, amount, bh, nonce=0):
    seed, pk = src
    msg = txn_lib.build_unsigned(
        [pk], bh,
        [(2, bytes([0, 1]), sysprog.ix_transfer(amount + nonce * 0))],
        extra_accounts=[dest, SYSTEM_PROGRAM_ID],
        readonly_unsigned_cnt=1)
    return txn_lib.assemble([ed.sign(seed, msg)], msg)


def test_wave_planning_conflicts_serialize():
    """Writers to one account land in distinct waves, in block order;
    disjoint txns share wave 0; a reader serializes after a writer."""
    payers = [_keypair(10 + i) for i in range(4)]
    bh = b"\x11" * 32
    shared = b"\x51" + bytes(31)
    d0, d1 = b"\x52" + bytes(31), b"\x53" + bytes(31)

    payloads = [
        _transfer(payers[0], shared, 1, bh),    # writes shared
        _transfer(payers[1], shared, 2, bh),    # writes shared -> wave 1
        _transfer(payers[2], d0, 3, bh),        # disjoint -> wave 0
        _transfer(payers[3], d1, 4, bh),        # disjoint -> wave 0
    ]

    def addrs_of(parsed, payload):
        a = list(parsed.account_addrs(payload))
        return a, [parsed.is_writable(i) for i in range(len(a))]

    waves = plan_waves(payloads, addrs_of)
    idx_wave = {p.idx: w for w, wave in enumerate(waves) for p in wave}
    assert idx_wave[0] == 0 and idx_wave[1] == 1     # conflict serializes
    assert idx_wave[2] == 0 and idx_wave[3] == 0     # disjoint in wave 0
    # block order preserved for the conflicting pair
    assert waves[0][0].idx == 0


@pytest.fixture
def chain():
    faucet_seed, faucet_pk = _keypair(1)
    g = gen_mod.create(faucet_pk, creation_time=1_700_000_000,
                       slots_per_epoch=64)
    payers = [_keypair(100 + i) for i in range(32)]
    for _, pk in payers:
        g.accounts[pk] = Account(lamports=1_000_000_000)
    return g, payers


def _block(g, payers, n_txn=32):
    bh = g.genesis_hash()
    poh = bytes(32)
    entries = []
    payloads = []
    for i in range(n_txn):
        dest = b"\xd0" + bytes(15) + i.to_bytes(16, "little")
        payloads.append(_transfer(payers[i % len(payers)], dest,
                                  1000 + i, bh))
    mix = entry_lib.txn_mixin(payloads)
    poh = entry_lib.next_hash(poh, 1, mix)
    entries.append(entry_lib.Entry(1, poh, payloads))
    poh = entry_lib.next_hash(poh, 1, None)
    entries.append(entry_lib.Entry(1, poh, []))
    return entries


@pytest.mark.parametrize("n_txn,workers", [(32, 4), (256, os.cpu_count())],
                         ids=["32txn-4workers", "256txn-allcores"])
def test_parallel_matches_serial_bank_hash(chain, n_txn, workers):
    g, payers = chain
    entries = _block(g, payers, n_txn=n_txn)

    rt_serial = Runtime(g)
    res_s = replay_mod.replay_slot(rt_serial, 1, entries, bytes(32))
    assert res_s.ok, res_s.err

    rt_par = Runtime(g)
    res_p = replay_mod.replay_slot(rt_par, 1, entries, bytes(32),
                                   workers=workers)
    assert res_p.ok, res_p.err
    assert res_p.bank_hash == res_s.bank_hash
    assert res_p.txn_cnt == res_s.txn_cnt == n_txn
    assert res_p.txn_fail_cnt == res_s.txn_fail_cnt == 0

    # state equivalence beyond the hash: spot-check a destination
    rt_serial.publish(1)
    rt_par.publish(1)
    dest = b"\xd0" + bytes(15) + (5).to_bytes(16, "little")
    assert rt_par.balance(dest) == rt_serial.balance(dest) == 1005


def test_parallel_with_conflicts_and_failures(chain):
    """Conflicting txns (same fee payer: writable account shared) are
    wave-serialized; duplicate transfers from one payer both land;
    failing txns (insufficient funds) fold in identically."""
    g, payers = chain
    bh = g.genesis_hash()
    poor_seed, poor_pk = _keypair(999)
    g.accounts[poor_pk] = Account(lamports=6_000)  # fee, no transfer
    payloads = []
    dest = b"\xdd" + bytes(31)
    for i in range(10):
        payloads.append(_transfer(payers[0], dest, 100, bh, nonce=i))
    payloads.append(_transfer((poor_seed, poor_pk), dest, 1_000_000, bh))
    for i in range(10):
        payloads.append(_transfer(payers[1 + i % 8], dest, 50, bh, nonce=i))
    poh = entry_lib.next_hash(bytes(32), 1, entry_lib.txn_mixin(payloads))
    entries = [entry_lib.Entry(1, poh, payloads)]

    rt_s = Runtime(g)
    res_s = replay_mod.replay_slot(rt_s, 1, entries, bytes(32))
    rt_p = Runtime(g)
    res_p = replay_mod.replay_slot(rt_p, 1, entries, bytes(32), workers=4)
    assert res_s.ok and res_p.ok
    assert res_p.bank_hash == res_s.bank_hash
    assert res_p.txn_fail_cnt == res_s.txn_fail_cnt >= 1
