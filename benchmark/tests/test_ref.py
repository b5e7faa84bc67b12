"""The plain reference (RFC 8032 verify, transaction parse) and the
generator's signatures held to it."""

import numpy as np
import pytest

from benchmark import gen
from benchmark.ref import ed25519 as ed
from benchmark.ref import txn as rtxn

# RFC 8032 section 7.1, TEST 1-3: (secret, public, message, signature)
RFC8032 = [
    ("9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60",
     "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a", "",
     "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e065224901555fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"),
    ("4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb",
     "3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c", "72",
     "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69da085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00"),
    ("c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7",
     "fc51cd8e6218a1a38da47ed00230f0580816ed13ba3303ac5deb911548908025", "af82",
     "6291d657deec24024827e69c3abe01a30ce548a284743a445e3680d7db5ac3ac18ff9b538d16f290ae67f760984dc6594a7c15e9716ed28dc027beceea1ec40a"),
]


@pytest.mark.parametrize("sk,pk,msg,sig", RFC8032)
def test_rfc8032_vectors(sk, pk, msg, sig):
    sk, pk, msg, sig = map(bytes.fromhex, (sk, pk, msg, sig))
    assert ed.public_key(sk) == pk
    assert ed.sign(sk, msg) == sig
    assert ed.verify(pk, msg, sig)
    assert not ed.verify(pk, msg + b"\0", sig)


def test_strict_rules():
    sk, pk, msg, sig = map(bytes.fromhex, RFC8032[1])
    s = int.from_bytes(sig[32:], "little")
    malleated = sig[:32] + (s + ed.L).to_bytes(32, "little")
    assert not ed.verify(pk, msg, malleated)            # S >= L
    assert ed.verify(pk, msg, malleated, canonical_s=False)
    small = ed.compress(ed.IDENTITY)                    # order 1
    assert not ed.verify(small, msg, sig)
    assert not ed.verify(pk, msg, small + sig[32:])


def test_chain_encodings_are_multiples_of_b():
    encs = ed.chain_encodings(ed.mul(1000, ed.B), ed.B, 5)
    assert encs == [ed.compress(ed.mul(1000 + i, ed.B)) for i in range(5)]


MIX = {"loop": "open", "rate_txn_s": 3000, "warmup_s": 0.1,
       "kinds": [{"name": "vote", "share": 0.5, "shape": "vote",
                  "msg": {"dist": "uniform", "lo": 200, "hi": 260}},
                 {"name": "other", "share": 0.5, "shape": "program",
                  "sigs": [[1, 0.4], [2, 0.3], [3, 0.3, 12]],
                  "msg": {"dist": "lognormal", "median": 300,
                          "sigma": 0.8}}],
       "dup_share": 0.1, "bad_share": 0.2,
       "payers": {"count": 64, "dist": "zipf", "s": 1.1}}


@pytest.fixture(scope="module")
def traffic():
    pool, sched, nkeys = gen.plan(MIX, 2**31 + 5, 0.2)
    pubs = gen.key_pubs(pool.seed, nkeys)
    parts = [gen.build_slice((pool.slice(lo, hi), pubs))
             for lo, hi in gen.slices(len(pool.nsig), 3)]
    return gen.assemble_traffic(pool, sched, parts)


def test_generated_signatures_verify_as_recorded(traffic):
    """Nonce-chain, many-signer and damaged transactions: the reference
    passes exactly the undamaged ones, and each kind of damage fails."""
    n = len(traffic.nsig)
    assert (traffic.nsig > 2).any() and (traffic.bad >= 0).any()
    seen = set()
    for p in range(n):
        wire = traffic.wires[traffic.offs[p]:traffic.offs[p + 1]]
        assert len(wire) <= rtxn.MTU
        sigs, pubs, msg = rtxn.parse(wire)
        assert len(sigs) == traffic.nsig[p]
        ok = all(ed.verify(k, msg, s) for s, k in zip(sigs, pubs))
        assert ok == (traffic.bad[p] < 0)
        if traffic.bad[p] >= 0:
            seen.add(int(traffic.bad[p]))
            lax = all(ed.verify(k, msg, s, canonical_s=False)
                      for s, k in zip(sigs, pubs))
            assert lax == (gen.BAD_KINDS[traffic.bad[p]] == "s_plus_l")
    assert seen == set(range(len(gen.BAD_KINDS)))


def test_tags_distinct_and_outcomes(traffic):
    assert len(np.unique(traffic.tags)) == len(traffic.tags)
    o = traffic.send_outcome
    assert set(np.unique(o)) == {gen.PASS, gen.FAIL, gen.DUP}
    dup = np.nonzero(o == gen.DUP)[0]
    first = {}
    for s, p in enumerate(traffic.send_pool):
        first.setdefault(int(p), s)
    for s in dup:      # a retransmission repeats an earlier send
        assert first[int(traffic.send_pool[s])] < s


def test_same_seed_same_bytes():
    pool, sched, nkeys = gen.plan(MIX, 7, 0.1)
    pubs = gen.key_pubs(7, nkeys)
    a = gen.build_slice((pool.slice(0, 20), pubs))
    b = gen.build_slice((pool.slice(0, 20), pubs))
    c = gen.build_slice((pool.slice(10, 20), pubs))
    assert a[0] == b[0]
    assert a[0].endswith(c[0])      # a slice is built alike anywhere


def test_txn_parse_rejects_malformed():
    with pytest.raises(ValueError):
        rtxn.parse(b"\x01" + bytes(10))
