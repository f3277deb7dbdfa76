"""Persistent compiled-program cache (mxnet_tpu/program_cache.py).

Covers the on-disk entry format (magic + fingerprint + checksum) and its
corruption rejections — truncated / magic / fingerprint / checksum / io
— with quarantine and ``program_cache_errors_total`` accounting, LRU
eviction under the byte cap, the enable/disable lifecycle (manifest +
jax call-path installation), cache placement (``resolve_dir``), the in-process call-path
roundtrip (a fresh jit wrapper restores from disk instead of
compiling), and the warm-restart acceptance: process A compiles and
persists, process B on the same cache dir reaches step 2 with ZERO
fresh XLA compiles (puts == misses == 0, zero ``XLA::Compile`` spans,
zero repeat-step op-jit misses), an env-flag flip recompiles, and
corrupted artifacts quarantine without taking the run down.
"""
import hashlib
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mxnet_tpu import program_cache, telemetry
from mxnet_tpu.program_cache import DiskProgramCache

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "program_cache_worker.py")
_FP = hashlib.sha256(b"test-env").digest()[:16]


@pytest.fixture(autouse=True)
def _clean():
    program_cache.disable()
    telemetry.reset()
    yield
    program_cache.disable()
    telemetry.reset()


def _error_count(kind):
    fam = telemetry.registry().get("program_cache_errors_total")
    for lv, v in (fam.samples() if fam is not None else []):
        if lv == (kind,):
            return v
    return 0.0


def _mk(tmp_path, max_bytes=0):
    return DiskProgramCache(str(tmp_path / "ns"), _FP, max_bytes)


# ---------------------------------------------------------------------------
# entry format + corruption handling
# ---------------------------------------------------------------------------
class TestDiskCache:
    def test_roundtrip(self, tmp_path):
        c = _mk(tmp_path)
        c.put("jit__step-abc123", b"executable-bytes")
        assert c.get("jit__step-abc123") == b"executable-bytes"
        assert c.stats["puts"] == 1 and c.stats["disk_hits"] == 1
        path = c._entry_path("jit__step-abc123")
        assert path.endswith(".mxpc") and os.path.exists(path)
        raw = open(path, "rb").read()
        assert raw.startswith(b"MXPC1\0")
        assert raw[6:22] == _FP
        assert raw[22:54] == hashlib.sha256(b"executable-bytes").digest()

    def test_absent_key_is_miss(self, tmp_path):
        c = _mk(tmp_path)
        assert c.get("never-put") is None
        assert c.stats["misses"] == 1 and c.stats["errors"] == 0

    def test_entry_path_is_sanitized(self, tmp_path):
        c = _mk(tmp_path)
        path = c._entry_path("jit/step:with spaces\x00and*junk")
        name = os.path.basename(path)
        assert all(ch.isalnum() or ch in "-_." for ch in name)
        c.put("jit/step:with spaces\x00and*junk", b"x")
        assert c.get("jit/step:with spaces\x00and*junk") == b"x"

    def _corrupt(self, tmp_path, mangle, kind):
        c = _mk(tmp_path)
        c.put("k", b"payload-bytes")
        path = c._entry_path("k")
        raw = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(mangle(raw))
        assert c.get("k") is None
        assert c.stats["errors"] == 1 and c.stats["misses"] == 1
        assert _error_count(kind) == 1
        qdir = os.path.join(c.directory, "quarantine")
        assert os.path.basename(path) in os.listdir(qdir)
        assert not os.path.exists(path)  # moved, not copied
        # cache recovers: a fresh put/get works
        c.put("k", b"payload-bytes")
        assert c.get("k") == b"payload-bytes"
        return c

    def test_truncated_rejected(self, tmp_path):
        self._corrupt(tmp_path, lambda raw: raw[:10], "truncated")

    def test_bad_magic_rejected(self, tmp_path):
        self._corrupt(tmp_path, lambda raw: b"NOTPC\0" + raw[6:], "magic")

    def test_foreign_fingerprint_rejected(self, tmp_path):
        other = hashlib.sha256(b"other-env").digest()[:16]
        self._corrupt(tmp_path,
                      lambda raw: raw[:6] + other + raw[22:], "fingerprint")

    def test_checksum_rejected(self, tmp_path):
        self._corrupt(
            tmp_path,
            lambda raw: raw[:-3] + bytes(b ^ 0xFF for b in raw[-3:]),
            "checksum")

    def test_unreadable_entry_is_io_error(self, tmp_path):
        c = _mk(tmp_path)
        os.makedirs(c._entry_path("k"))  # open() -> IsADirectoryError
        assert c.get("k") is None
        assert _error_count("io") == 1 and c.stats["errors"] == 1

    def test_lru_eviction(self, tmp_path):
        # entry = 54B header + 1000B payload; cap fits two entries
        c = _mk(tmp_path, max_bytes=2200)
        c.put("k1", b"a" * 1000)
        c.put("k2", b"b" * 1000)
        old = os.path.getmtime(c._entry_path("k2")) - 1000
        os.utime(c._entry_path("k1"), (old, old))  # k1 = least recent
        c.put("k3", b"c" * 1000)
        assert c.stats["evictions"] == 1
        assert not os.path.exists(c._entry_path("k1"))
        assert c.get("k2") == b"b" * 1000
        assert c.get("k3") == b"c" * 1000


# ---------------------------------------------------------------------------
# lifecycle + env activation
# ---------------------------------------------------------------------------
class TestLifecycle:
    def test_enable_writes_manifest_in_place(self, tmp_path):
        c = program_cache.enable(str(tmp_path))
        assert c is not None and program_cache.enabled()
        # entries live directly in the directory the caller placed
        assert c.directory == str(tmp_path)
        manifest = json.load(open(os.path.join(c.directory,
                                               "manifest.json")))
        assert manifest["fingerprint"] == c.fingerprint_hex
        assert program_cache.fingerprint() == c.fingerprint_hex
        s = program_cache.stats()
        assert s["enabled"] and s["dir"] == str(tmp_path)
        import jax
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        program_cache.disable()
        assert not program_cache.enabled()
        assert program_cache.stats() == {"enabled": False, "memory_hits": 0}

    def test_enable_is_idempotent(self, tmp_path):
        c1 = program_cache.enable(str(tmp_path))
        c2 = program_cache.enable(str(tmp_path / "other"))
        assert c1 is c2

    def test_ensure_enabled_reads_env(self, tmp_path, monkeypatch):
        monkeypatch.delenv(program_cache.JAX_ENV_DIR, raising=False)
        monkeypatch.setenv(program_cache.ENV_DIR, str(tmp_path))
        assert program_cache.ensure_enabled()
        assert program_cache.cache_dir() == str(tmp_path)

    def test_gate_force_disables(self, tmp_path, monkeypatch):
        monkeypatch.setenv(program_cache.ENV_DIR, str(tmp_path))
        monkeypatch.setenv(program_cache.ENV_GATE, "0")
        assert not program_cache.ensure_enabled()
        assert not program_cache.enabled()

    def test_ensure_enabled_without_dir(self, monkeypatch):
        monkeypatch.delenv(program_cache.ENV_DIR, raising=False)
        assert not program_cache.ensure_enabled()

    def test_memory_hits_counted(self, tmp_path):
        program_cache.enable(str(tmp_path))
        program_cache.note_memory_hit()
        assert program_cache.stats()["memory_hits"] == 1

    def test_put_count_accessor(self, tmp_path):
        assert program_cache.put_count() is None
        c = program_cache.enable(str(tmp_path))
        assert program_cache.put_count() == 0
        c.put("k", b"v")
        assert program_cache.put_count() == 1


# ---------------------------------------------------------------------------
# jax call path: a fresh jit wrapper restores instead of compiling
# ---------------------------------------------------------------------------
def _affine(x):
    return x * 2.0 + 1.0


class TestCallPath:
    def test_disk_restore_in_process(self, tmp_path):
        c = program_cache.enable(str(tmp_path))
        import jax
        import jax.numpy as jnp
        # start from an empty in-process jit cache so every helper
        # program (ones/convert_element_type) compiles — and puts —
        # under THIS cache, regardless of what earlier tests warmed
        jax.clear_caches()
        jax.jit(_affine)(jnp.ones((4,))).block_until_ready()
        puts = c.stats["puts"]
        assert puts >= 1
        # same function through an EMPTY in-process cache (jit wrappers
        # can share the global C++ pjit cache by function identity) —
        # the new compile request must be served from disk
        jax.clear_caches()
        jax.jit(_affine)(jnp.ones((4,))).block_until_ready()
        assert c.stats["disk_hits"] >= 1
        assert c.stats["puts"] == puts


# ---------------------------------------------------------------------------
# warm restart across real process boundaries
# ---------------------------------------------------------------------------
def _run_worker(cache_dir, extra_env=None):
    env = dict(os.environ)
    # a throw-away directory is the point here: jax's own placement
    # variable would win over it (program_cache.resolve_dir)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["MXNET_PROGRAM_CACHE_DIR"] = str(cache_dir)
    env.update(extra_env or {})
    proc = subprocess.run([sys.executable, WORKER], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


class TestWarmRestart:
    def test_zero_compile_restart_and_env_flip(self, tmp_path):
        cold = _run_worker(tmp_path)
        assert cold["ok"] and cold["cache_enabled"]
        assert cold["puts"] > 0 and cold["disk_hits"] == 0
        assert cold["compile_spans"] >= 1
        assert cold["repeat_op_jit_misses"] == 0

        # process B, same cache dir: ready for step 1 with ZERO fresh
        # XLA compiles — the deploy-prefill contract
        warm = _run_worker(tmp_path)
        assert warm["ok"]
        assert warm["puts"] == 0 and warm["misses"] == 0
        assert warm["disk_hits"] > 0
        assert warm["compile_spans"] == 0
        assert warm["restore_spans"] >= 1
        assert warm["repeat_op_jit_misses"] == 0

        # flipping a step cache-key env flag changes the traced
        # programs: the stale executables must NOT be served
        flipped = _run_worker(tmp_path, {"MXNET_TPU_FUSED_STEP": "0"})
        assert flipped["ok"]
        assert flipped["puts"] > 0 and flipped["misses"] > 0

    def test_corrupted_artifacts_never_poison_a_run(self, tmp_path):
        cold = _run_worker(tmp_path)
        assert cold["puts"] > 0
        entries = []
        for root, _dirs, files in os.walk(tmp_path):
            if os.path.basename(root) == "quarantine":
                continue
            entries += [os.path.join(root, f) for f in files
                        if f.endswith(".mxpc")]
        assert entries
        for path in entries:
            raw = open(path, "rb").read()
            with open(path, "wb") as f:  # bit-rot the payload tail
                f.write(raw[:-3] + bytes(b ^ 0xFF for b in raw[-3:]))
        hurt = _run_worker(tmp_path)
        assert hurt["ok"], "corrupted cache must not take the run down"
        assert hurt["errors"] == len(entries)
        assert hurt["disk_hits"] == 0 and hurt["puts"] > 0
        qfiles = []
        for root, _dirs, files in os.walk(tmp_path):
            if os.path.basename(root) == "quarantine":
                qfiles += files
        assert len(qfiles) == len(entries)
