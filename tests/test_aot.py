"""AOT executable store (utils/aot.py): the warm-boot artifact behind the
multi-process verify topology (VERDICT r4 #2).  Mechanics are tested with a
tiny graph — the verify-graph integration is exercised by the bench's
measure_mp_vps and tests/test_topo_run.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from firedancer_tpu.utils import aot


def _tiny_compiled():
    def f(x, y):
        return (x * 2 + y).sum(axis=0)

    args = (jnp.zeros((8, 16), jnp.float32), jnp.ones((8, 16), jnp.float32))
    return jax.jit(f).lower(*args).compile(), args


def test_roundtrip(tmp_path):
    if jax.default_backend() == "cpu":
        pytest.skip("this jaxlib's XLA:CPU AOT loader rejects artifacts "
                    "across machine-feature sets; the TPU path is covered "
                    "by bench.py measure_mp_vps")
    compiled, args = _tiny_compiled()
    k = aot.key("tiny", 8, 16)
    path = aot.save(str(tmp_path), k, compiled)
    assert path.endswith(k)
    fn = aot.load(str(tmp_path), k)
    assert fn is not None
    got = np.asarray(fn(*args))
    want = np.asarray(compiled(*args))
    np.testing.assert_array_equal(got, want)


def test_key_varies_by_shape_and_backend():
    assert aot.key("verify", 2048, 256) != aot.key("verify", 1024, 256)
    assert jax.default_backend() in aot.key("verify", 2048, 256)


def test_key_starts_no_backend():
    """A parent that builds a key must not claim the chip its tile
    processes need."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("from firedancer_tpu.utils import aot; aot.key('verify', 1, 2); "
            "from jax._src import xla_bridge; "
            "print(xla_bridge.backends_are_initialized())")
    out = subprocess.run([sys.executable, "-c", code], cwd=repo,
                         env=dict(os.environ, PYTHONPATH=repo),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[-1] == "False"


def test_load_miss_returns_none(tmp_path):
    assert aot.load(str(tmp_path), "nope.aotx") is None


def test_load_corrupt_returns_none(tmp_path):
    p = tmp_path / "bad.aotx"
    p.write_bytes(b"\x80\x04 definitely not a pickled executable")
    assert aot.load(str(tmp_path), "bad.aotx") is None


def _keyed(monkeypatch, tmp_path):
    """Isolate the HMAC master key under tmp_path (no ~/.cache writes)."""
    monkeypatch.setenv(aot._KEY_ENV, str(tmp_path / "master.key"))


def _fake_artifact(tmp_path, k="fake.aotx", blob=b"not-a-real-executable"):
    """A correctly-framed artifact: MAGIC | hmac(store_key, blob) | blob.
    The blob is not a valid pickle payload, but authentication runs FIRST
    — these tests only care which frames reach the unpickler at all."""
    import hashlib
    import hmac

    tag = hmac.new(aot._store_key(str(tmp_path)), blob,
                   hashlib.sha256).digest()
    (tmp_path / k).write_bytes(aot._MAGIC + tag + blob)
    return k


def test_load_refuses_unsigned_legacy_pickle(monkeypatch, tmp_path):
    """A raw pickle (pre-HMAC store, or attacker-planted) is refused
    without ever reaching pickle.loads — unpickling hostile bytes is code
    execution."""
    import pickle

    _keyed(monkeypatch, tmp_path)

    class Boom:
        def __reduce__(self):
            return (pytest.fail, ("unsigned pickle was deserialized!",))

    (tmp_path / "legacy.aotx").write_bytes(pickle.dumps(Boom()))
    assert aot.load(str(tmp_path), "legacy.aotx") is None


def test_load_refuses_tampered_blob(monkeypatch, tmp_path):
    import pickle

    _keyed(monkeypatch, tmp_path)

    class Boom:
        def __reduce__(self):
            return (pytest.fail, ("tampered pickle was deserialized!",))

    k = _fake_artifact(tmp_path, blob=pickle.dumps(Boom()))
    raw = bytearray((tmp_path / k).read_bytes())
    raw[-1] ^= 0x01                          # flip one payload bit
    (tmp_path / k).write_bytes(bytes(raw))
    assert aot.load(str(tmp_path), k) is None
    raw = bytearray((tmp_path / k).read_bytes())
    raw[-1] ^= 0x01                          # restore payload ...
    raw[len(aot._MAGIC)] ^= 0x01             # ... corrupt the tag instead
    (tmp_path / k).write_bytes(bytes(raw))
    assert aot.load(str(tmp_path), k) is None


def test_well_signed_frame_reaches_unpickler(monkeypatch, tmp_path):
    """The positive control for the two refusal tests: an authentic frame
    gets PAST the HMAC gate (then fails pickle/deserialize gracefully)."""
    _keyed(monkeypatch, tmp_path)
    k = _fake_artifact(tmp_path)             # authentic tag, garbage blob
    assert aot.load(str(tmp_path), k) is None  # graceful: no exception


def test_store_key_binds_store_path(monkeypatch, tmp_path):
    """An artifact copied between stores re-verifies only under the same
    directory: the store realpath is mixed into the per-store key."""
    _keyed(monkeypatch, tmp_path)
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir(), b.mkdir()
    assert aot._store_key(str(a)) != aot._store_key(str(b))
    k = _fake_artifact(a)
    (b / k).write_bytes((a / k).read_bytes())
    import hashlib
    import hmac

    raw = (b / k).read_bytes()
    hlen = len(aot._MAGIC) + 32
    tag, blob = raw[len(aot._MAGIC):hlen], raw[hlen:]
    assert not hmac.compare_digest(
        tag, hmac.new(aot._store_key(str(b)), blob,
                      hashlib.sha256).digest())


def test_master_key_created_0600_and_stable(monkeypatch, tmp_path):
    import os
    import stat

    _keyed(monkeypatch, tmp_path)
    k1 = aot._master_key()
    k2 = aot._master_key()
    assert k1 == k2 and len(k1) >= 32
    mode = os.stat(tmp_path / "master.key").st_mode
    assert stat.S_IMODE(mode) == 0o600


def test_verify_tile_aot_require_fails_loudly(tmp_path):
    """A verify tile told to boot AOT-only must die with a clear error on
    a store miss, not silently cold-compile for minutes."""
    from firedancer_tpu.disco.tiles import VerifyTile

    class Ctx:
        cfg = {"batch": 16, "msg_maxlen": 256, "aot_dir": str(tmp_path),
               "aot_require": True}

    with pytest.raises(RuntimeError, match="refusing to cold-compile"):
        VerifyTile().init(Ctx())
