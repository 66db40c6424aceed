"""Test bootstrap: run JAX on a virtual 8-device CPU mesh.

This is the envtest analog from the reference test strategy (reference
``internal/controller/suite_test.go`` boots a real kube-apiserver without a
cluster): we boot JAX with 8 virtual CPU devices so all sharding/mesh code
paths compile and execute without TPU hardware.

``JAX_PLATFORMS=cpu`` in the environment is honoured by JAX and is how
the tier-1 command runs the suite; the ``jax.config.update`` below forces
the CPU as well, so a bare ``pytest`` on a machine with an accelerator
still runs here and never takes the chip from another process. The
XLA_FLAGS append works because the CPU backend initializes lazily. The
chip itself is exercised by ``python chip_smoke.py`` (one process per
chip), never by this suite; ``tests/test_tpu_compile.py`` only compiles
for a described chip.
"""

import collections
import os
import shutil
import subprocess
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

# Persistent XLA compilation cache: the suite is ~95% XLA:CPU compile
# time (every engine fixture jits a fresh model), and the cache keys on
# HLO hash, so re-runs of an unchanged compiler produce byte-identical
# HLO and skip compilation entirely. First run warms it (~10 min);
# subsequent runs finish in ~1-2 min. Kept under tests/ so `git clean`
# or a compiler change naturally invalidates it.
# CKO_COMPILE_CACHE_DIR (the process-wide knob the sidecar and the
# ftw chunk children share — CI caches it between runs) overrides the
# tests-local default. configure_persistent_cache is the ONE place the
# cache is wired (abspath, thresholds, jax cache-latch reset).
_cache_dir = os.environ.get("CKO_COMPILE_CACHE_DIR") or os.path.join(
    os.path.dirname(__file__), ".jax_cache"
)
from coraza_kubernetes_operator_tpu.engine.compile_cache import (  # noqa: E402
    configure_persistent_cache,
)

configure_persistent_cache(_cache_dir)

# Crash-proof cache writes: jaxlib 0.9.0's ``executable.serialize()``
# SIGSEGVs on certain XLA:CPU executables (reproduced deterministically
# on the crs-lite response-phase program — /tmp-level repros in round 4),
# killing the whole pytest run at cache-write time. Writes are wrapped in
# a fork: the child performs the real serialize+write and any crash dies
# with the child; a hung child is killed after a deadline. Cache READS
# (the fast path) are untouched, and good executables still get cached.
from jax._src import compilation_cache as _cc  # noqa: E402

_orig_put = _cc.put_executable_and_time


def _forked_put(*args, **kwargs):
    pid = os.fork()
    if pid == 0:
        code = 1
        try:
            _orig_put(*args, **kwargs)
            code = 0
        except BaseException:
            pass
        finally:
            os._exit(code)
    import time as _time

    deadline = _time.monotonic() + 120
    while _time.monotonic() < deadline:
        done, status = os.waitpid(pid, os.WNOHANG)
        if done:
            if status != 0:
                sys.stderr.write(
                    f"conftest: cache write skipped (child status {status})\n"
                )
            return
        _time.sleep(0.05)
    import signal as _signal

    os.kill(pid, _signal.SIGKILL)
    os.waitpid(pid, 0)
    sys.stderr.write("conftest: cache write child timed out; skipped\n")


_cc.put_executable_and_time = _forked_put


# -- the native library, for the test files that ask for it -------------------
#
# ``native/libcko_native.so`` is git-ignored and a fresh checkout has
# none, so the suite runs the Python tensorizer unless a test asks for
# the library through the fixtures below. They build it from the
# committed source and load it for one module (or one engine) at a time;
# the load is undone afterwards, so no other test file of the worker
# sees a native library it did not ask for.

import pytest  # noqa: E402

import coraza_kubernetes_operator_tpu.native as _native  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="session")
def native_lib(tmp_path_factory):
    """libcko_native.so built from the committed source (as
    wafbench/harness.py builds its own); skips only without a compiler.

    Session scope is per xdist worker: each worker that is handed a file
    using this builds its own copy (about 9 s) into its own temporary
    directory, so there is no lock and nothing shared to race on."""
    if shutil.which("make") is None or shutil.which("g++") is None:
        pytest.skip("no C++ compiler to build the native library with")
    lib = tmp_path_factory.mktemp("native") / "libcko_native.so"
    proc = subprocess.run(
        ["make", "-C", os.path.join(_REPO, "native"), f"TARGET={lib}"],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0 and lib.exists(), proc.stdout + proc.stderr
    return lib


def load_native(mp, lib_path):
    """Point load_library() at ``lib_path`` (None: no library) until
    ``mp`` is undone."""
    mp.setattr(_native, "_lib", None)
    mp.setenv("CKO_NATIVE", "1")
    if lib_path is None:
        mp.setenv("CKO_NATIVE_LIB", "/nonexistent/libcko_native.so")
    else:
        mp.setenv("CKO_NATIVE_LIB", str(lib_path))


def native_engine(rules, lib_path):
    """A ``WafEngine`` built with ``lib_path`` loaded (None: with no
    library, so on the Python tensorizer); it keeps its tensorizer after
    the load is undone."""
    from coraza_kubernetes_operator_tpu.engine import WafEngine

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("CKO_AUTOMATA", "1")
        load_native(mp, lib_path)
        return WafEngine(rules)


@pytest.fixture(scope="module")
def native_loaded(native_lib):
    """The built library loaded for every test of the module, for files
    whose tests build engines or call ``load_library()`` themselves
    (``pytestmark = pytest.mark.usefixtures("native_loaded")``)."""
    with pytest.MonkeyPatch.context() as mp:
        load_native(mp, native_lib)
        yield native_lib


def dfa_witness(dfa) -> bytes:
    """A shortest input the exact DFA matches (BFS over states), spelled
    with plain lowercase bytes where a class has one, so that crs-lite's
    pipelines (lowercase, urlDecodeUni, …) leave it alone."""
    pref = list(b"abcdefghijklmnopqrstuvwxyz0123456789 =<>()/.;:-_'\"") + list(range(256))
    rep = {}
    for b in pref:
        rep.setdefault(int(dfa.classmap[b]), b)
    if dfa.always_match or dfa.match_end[0]:
        return b""
    seen = {0: b""}
    todo = collections.deque([0])
    while todo:
        s = todo.popleft()
        for c, b in rep.items():
            path = seen[s] + bytes([b])
            if dfa.emit[s, c]:
                return path
            nxt = int(dfa.trans[s, c])
            if nxt not in seen:
                seen[nxt] = path
                if dfa.match_end[nxt]:
                    return path
                todo.append(nxt)
    raise AssertionError("the DFA matches nothing")


def layout_pin(model) -> dict:
    """What a model's layout rests on, as plain JSON: the column order,
    the blocks' kinds and costs (the masks and row partitions of
    ``tier_tensors``), which blocks ride flat bins, the columns the host
    confirms, and every bin's slot layout. ``tests/data/layout_pins.json``
    holds these as the parent of PR 48 built them."""
    return {
        "group_order": [int(g) for g in model.group_order],
        "block_kinds": [[int(k) for k in ks] for ks in model.block_kinds],
        "block_cost": [float(c) for c in model.block_cost],
        "flat_covered": [int(b) for b in model.flat_covered],
        "prefilter_cols": [[int(c), int(g)] for c, g in model.prefilter_cols],
        "bins": [
            {
                "pieces": [[int(x) for x in p] for p in fb.pieces],
                "seg_pipes": [int(p) for p in fb.seg_pipes],
                "seg_slots": [int(n) for n in fb.seg_slots],
            }
            for fb in model.flat_banks
        ],
    }


def layout_pin_sha256(pin: dict) -> str:
    import hashlib
    import json

    return hashlib.sha256(
        json.dumps(pin, sort_keys=True, separators=(",", ":")).encode()
    ).hexdigest()
