"""The comparison that decides `correct`: a sound verdict stream passes,
the control and each fault a served verifier can have fail it."""

import numpy as np
import pytest

from benchmark import check, gen
from benchmark.tests.test_ref import MIX


@pytest.fixture(scope="module")
def sent():
    mix = dict(MIX, bad_share=0.05)
    pool, sched, nkeys = gen.plan(mix, 2**31 + 9, 0.3)
    pubs = gen.key_pubs(pool.seed, nkeys)
    tr = gen.assemble_traffic(pool, sched, [gen.build_slice(
        (pool, pubs))])
    due = (tr.send_due * 1e9).astype(np.int64)
    return tr, due


def sound_stream(tr, due):
    """What a correct system publishes: one verdict per PASS send."""
    ok = np.nonzero(tr.send_outcome == gen.PASS)[0]
    p = tr.send_pool[ok]
    return {"t": due[ok] + 2_000_000, "tag": tr.tags[p].copy(),
            "digest": tr.digest[p].copy()}


def counts(tr, obs):
    deliv, bad = check.match(tr, tr.send_pool, tr.send_outcome, obs)
    missing = int(((tr.send_outcome == gen.PASS) & (deliv < 0)).sum())
    return dict(bad, missing=missing), deliv


def test_sound_stream_is_correct(sent):
    tr, due = sent
    c, deliv = counts(tr, sound_stream(tr, due))
    assert c == {"unknown": 0, "altered": 0, "wrong_accept": 0,
                 "missing": 0}
    ok = tr.send_outcome == gen.PASS
    assert (deliv[ok] == due[ok] + 2_000_000).all()


def test_out_of_order_verdicts_match(sent):
    tr, due = sent
    obs = sound_stream(tr, due)
    perm = np.random.default_rng(1).permutation(len(obs["t"]))
    c, _ = counts(tr, {k: v[perm] for k, v in obs.items()})
    assert not any(c.values())


def test_half_left_out(sent):
    tr, due = sent
    obs = {k: v[::2] for k, v in sound_stream(tr, due).items()}
    c, _ = counts(tr, obs)
    assert c["missing"] > 0


def test_answer_altered_where_produced(sent):
    """A damaged transaction passed, a verdict's bytes changed, a verdict
    published twice, a verdict for a transaction never sent."""
    tr, due = sent
    base = sound_stream(tr, due)
    bad_p = int(np.nonzero(tr.bad >= 0)[0][0])
    for fault, key in [
            (lambda o: {k: np.r_[v, [{"t": 1, "tag": tr.tags[bad_p],
                                      "digest": tr.digest[bad_p]}[k]]]
                        for k, v in o.items()}, "wrong_accept"),
            (lambda o: dict(o, digest=o["digest"] ^ np.uint64(1)),
             "altered"),
            (lambda o: {k: np.r_[v, v[:1]] for k, v in o.items()},
             "wrong_accept"),
            (lambda o: dict(o, tag=o["tag"] ^ np.uint64(1 << 40)),
             "unknown")]:
        c, _ = counts(tr, fault({k: v.copy() for k, v in base.items()}))
        assert c[key] > 0, key


def test_control_is_not_correct(sent):
    """The lax reference in the program's place accepts S + L."""
    tr, due = sent
    assert (tr.bad == gen.BAD_KINDS.index("s_plus_l")).any()
    obs = check.control_observed(tr, tr.send_pool, tr.send_outcome, due)
    c, _ = counts(tr, obs)
    assert c["wrong_accept"] == int(
        ((tr.bad[tr.send_pool] == gen.BAD_KINDS.index("s_plus_l"))
         & (tr.send_outcome == gen.FAIL)).sum()) > 0


def test_reference_sample_holds_every_damage_kind(sent):
    tr, _ = sent
    win = np.ones(len(tr.send_pool), bool)
    idx, ok = check.reference_sample(tr, tr.send_pool, win, 3, n=20)
    kinds = set(tr.bad[tr.send_pool[idx]].tolist())
    assert kinds >= set(range(len(gen.BAD_KINDS)))
    assert (ok == (tr.bad[tr.send_pool[idx]] < 0)).all()


def test_percentile_nearest_rank():
    x = np.r_[np.arange(1, 100), np.inf]
    assert check.percentile(x, 50) == 50
    assert check.percentile(x, 99) == 99
    assert check.percentile(np.r_[x, np.inf], 99) == np.inf
