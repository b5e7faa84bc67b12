"""How `correct` is decided, and the end-to-end metrics of a run.

Every send has the outcome its construction gives it (gen.py): PASS sends
must each get exactly one verdict published by dedup, carrying the bytes
that were sent; FAIL and DUP sends must get none.  The construction is
held to the plain reference (ref/): on a sample of the window's sends,
drawn from the seed and holding the longest transaction, every kind of
damage and many-signature transactions, the reference's verdict must be
the construction's.  Each number compared is exact, with the limit 0.
"""

import numpy as np

from . import gen
from .ref import ed25519 as ed
from .ref import txn as rtxn

LIMITS = {
    "missing": 0,        # PASS sends that never got a verdict
    "wrong_accept": 0,   # verdicts for FAIL or DUP sends, or one too many
    "unknown": 0,        # verdicts for transactions nobody sent
    "altered": 0,        # verdicts whose bytes are not the bytes sent
    "ref_mismatch": 0,   # sampled sends where the reference disagrees
    "fallback_lanes": 0,  # lanes the verify tile served off the CPU
    "window_compiles": 0,  # device programs compiled inside the window
}


def _occurrence(keys: np.ndarray) -> np.ndarray:
    """For each element, how many equal keys come before it."""
    idx = np.argsort(keys, kind="stable")
    k = keys[idx]
    start = np.r_[0, np.nonzero(k[1:] != k[:-1])[0] + 1]
    first = np.repeat(start, np.diff(np.r_[start, len(k)]))
    out = np.empty(len(keys), np.int64)
    out[idx] = np.arange(len(k)) - first
    return out


def match(traffic: gen.Traffic, send_pool, send_outcome, obs: dict):
    """Pair each verdict with a send: the k-th verdict of a transaction
    belongs to its k-th PASS send.  Returns (verdict time per send, -1 for
    none; counts of unknown, altered and wrong_accept verdicts)."""
    tags, t, dig = obs["tag"], obs["t"], obs["digest"]
    order = np.argsort(traffic.tags, kind="stable")
    stags = traffic.tags[order]
    pos = np.minimum(np.searchsorted(stags, tags), max(len(stags) - 1, 0))
    known = (stags[pos] == tags) if len(stags) else np.zeros(len(tags), bool)
    dpool = np.where(known, order[pos], -1)
    altered = int((known & (dig != traffic.digest[np.maximum(dpool, 0)]))
                  .sum())
    ps = np.nonzero(send_outcome == gen.PASS)[0]
    shift = np.int64(1) << np.int64(32)
    s_key = send_pool[ps].astype(np.int64) * shift + _occurrence(
        send_pool[ps].astype(np.int64))
    di = np.nonzero(known)[0]
    d_key = dpool[di].astype(np.int64) * shift + _occurrence(
        dpool[di].astype(np.int64))
    so = np.argsort(s_key)
    sk = s_key[so]
    j = np.minimum(np.searchsorted(sk, d_key), max(len(sk) - 1, 0))
    hit = (sk[j] == d_key) if len(sk) else np.zeros(len(d_key), bool)
    deliv = np.full(len(send_pool), -1, np.int64)
    deliv[ps[so[j[hit]]]] = t[di[hit]]
    return deliv, {"unknown": int((~known).sum()), "altered": altered,
                   "wrong_accept": int((~hit).sum())}


def window_mask(rec) -> np.ndarray:
    return (rec.send_due >= rec.w0) & (rec.send_due < rec.w1)


def reference_sample(traffic: gen.Traffic, send_pool, win, seed: int,
                     n: int = 192):
    """Sends of the window to hold to the reference: n drawn from the
    seed, the longest, up to 8 of each kind of damage and 16 with three
    or more signatures.  Returns (send indexes, reference verdicts)."""
    cand = np.nonzero(win)[0]
    if not len(cand):
        return cand, np.zeros(0, bool)
    rng = np.random.default_rng(seed & (2**63 - 1) ^ 0xC4EC)
    pools = send_pool[cand]
    lens = np.diff(traffic.offs)[pools]
    pick = set(rng.choice(cand, min(n, len(cand)), replace=False).tolist())
    pick.add(int(cand[np.argmax(lens)]))
    for kind in range(len(gen.BAD_KINDS)):
        pick.update(cand[traffic.bad[pools] == kind][:8].tolist())
    pick.update(cand[traffic.nsig[pools] >= 3][:16].tolist())
    idx = np.array(sorted(pick), np.int64)
    ok = np.array([reference_verdict(traffic, int(send_pool[s]))
                   for s in idx], bool)
    return idx, ok


def reference_verdict(traffic: gen.Traffic, p: int,
                      canonical_s: bool = True) -> bool:
    wire = traffic.wires[traffic.offs[p]:traffic.offs[p + 1]]
    try:
        sigs, pubs, msg = rtxn.parse(wire)
    except ValueError:
        return False
    return all(ed.verify(k, msg, s, canonical_s) for s, k in zip(sigs, pubs))


def decide(rec, seed: int) -> tuple[dict, np.ndarray, int]:
    """The numbers compared, each (value, limit); the verdict time of each
    send; and how many sends of the window failed."""
    deliv, bad = match(rec.traffic, rec.send_pool, rec.send_outcome, rec.obs)
    win = window_mask(rec)
    idx, ref_ok = reference_sample(rec.traffic, rec.send_pool, win, seed)
    built_ok = rec.traffic.bad[rec.send_pool[idx]] < 0
    missing = (rec.send_outcome == gen.PASS) & (deliv < 0)
    v0, w1, end = (rec.counters["w0"]["verify:0"],
                   rec.counters["w1"]["verify:0"], rec.counters["end"])
    ve = end["verify:0"]
    values = {
        "missing": int(missing.sum()),
        "wrong_accept": bad["wrong_accept"],
        "unknown": bad["unknown"],
        "altered": bad["altered"],
        "ref_mismatch": int((ref_ok != built_ok).sum()),
        "fallback_lanes": int(ve["fallback_lane_cnt"] + ve["device_fail_cnt"]
                              + ve["degraded_mode"]),
        "window_compiles": int(w1["compile_cnt"] - v0["compile_cnt"]),
    }
    checks = {k: (v, LIMITS[k]) for k, v in values.items()}
    failed = int((missing & win).sum()) + bad["wrong_accept"] \
        + bad["unknown"] + bad["altered"]
    return checks, deliv, failed


def control_observed(traffic: gen.Traffic, send_pool, send_outcome,
                     send_due) -> dict:
    """The verdict stream of the control: the plain reference with its
    canonical-S rule dropped (S reduced mod L, the lax acceptance the
    strict configurations rule out), put in the program's place.  A
    verdict is published 1 ms after each send it accepts, once per
    transaction and send cycle, as dedup would."""
    t, tags, digs = [], [], []
    verdict = {}
    for s in range(len(send_pool)):
        if send_outcome[s] == gen.DUP:
            continue
        p = int(send_pool[s])
        if traffic.bad[p] < 0:
            ok = True           # the strict reference accepts it, so the
        else:                   # lax one does (it accepts a superset)
            if p not in verdict:
                verdict[p] = reference_verdict(traffic, p, False)
            ok = verdict[p]
        if ok:
            t.append(int(send_due[s]) + 1_000_000)
            tags.append(traffic.tags[p])
            digs.append(traffic.digest[p])
    return {"t": np.array(t, np.int64), "tag": np.array(tags, np.uint64),
            "digest": np.array(digs, np.uint64)}


def percentile(x: np.ndarray, q: float) -> float:
    """Nearest-rank percentile of raw samples (inf counts as a sample)."""
    if not len(x):
        return float("nan")
    s = np.sort(x)
    return float(s[max(0, int(np.ceil(q / 100.0 * len(s))) - 1)])


def end_to_end(rec, deliv: np.ndarray, seconds: float) -> dict:
    """sigs_per_s, p50/p99_verdict_ms (open loops) and setup_s."""
    win = window_mask(rec)
    ok = win & (rec.send_outcome == gen.PASS)
    done = ok & (deliv >= 0) & (deliv <= rec.w1)
    nsig = rec.traffic.nsig[rec.send_pool].astype(np.int64)
    out = {"setup_s": rec.setup_s,
           "sigs_per_s": float(nsig[done].sum()) / seconds}
    if rec.loop == "open":
        # a send with no verdict counts as waiting until the run stopped
        # waiting: past any limit, and still a number
        t = np.where(deliv[ok] >= 0, deliv[ok], rec.t_end)
        lat = (t - rec.send_due[ok]) / 1e6
        out["p50_verdict_ms"] = percentile(lat, 50)
        out["p99_verdict_ms"] = percentile(lat, 99)
    return out
