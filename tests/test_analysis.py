"""Tests for the static invariant linter (repro.analysis.lint).

Each rule gets three golden snippets: a violating one (flagged with the
right rule id and line), the same snippet with a ``# repro: allow[Rn]``
suppression (passes), and a clean rewrite (passes). Plus framework-level
coverage: reporters, CLI exit codes, and the guarantee the shipped tree
itself lints clean.
"""
import dataclasses
import json
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis.lint import (
    all_rules,
    lint_paths,
    lint_source,
    render_json,
    split_findings,
)
from repro.analysis.lint.rules import COUNTERS_SCALAR_FIELDS

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src"


def _active(source, select=None):
    return [f for f in lint_source(source, select=select) if not f.suppressed]


def _suppressed(source, select=None):
    return [f for f in lint_source(source, select=select) if f.suppressed]


def _ids(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------------------ R1
R1_BAD = """
def f(c):
    c.cache_hits += 1
    c.storage_read_bytes = c.storage_read_bytes + 4096
"""
R1_ALLOWED = """
def f(c):
    c.cache_hits += 1  # repro: allow[R1] -- single-threaded tool
"""
R1_CLEAN = """
def f(c):
    c.bump("cache_hits")
    c.bump_many(storage_read_bytes=4096, storage_read_ops=1)
    hits = c.cache_hits          # reads are fine
    other.cache_rate += 1        # not a Counters field
"""
R1_INSIDE_CLASS = """
class Counters:
    def bump(self, n):
        self.cache_hits += n     # the locked mutator itself
"""


def test_r1_flags_direct_counter_mutation():
    fs = _active(R1_BAD, select=["R1"])
    assert _ids(fs) == ["R1", "R1"]
    assert fs[0].line == 3 and fs[1].line == 4
    assert "cache_hits" in fs[0].message


def test_r1_suppression_and_clean():
    assert _active(R1_ALLOWED, select=["R1"]) == []
    assert len(_suppressed(R1_ALLOWED, select=["R1"])) == 1
    assert _active(R1_CLEAN, select=["R1"]) == []
    assert _active(R1_INSIDE_CLASS, select=["R1"]) == []


def test_r1_field_list_matches_counters_dataclass():
    """The linter's hardcoded field set must track the real dataclass —
    drift would silently stop flagging new counters."""
    from repro.core.counters import Counters

    real = {f.name for f in dataclasses.fields(Counters)}
    assert real == set(COUNTERS_SCALAR_FIELDS)


# ------------------------------------------------------------------ R2
R2_BAD = """
def evict(self):
    with self._lock:
        self.storage.write_rows("f", 0, arr)
"""
R2_BAD_QUEUE = """
def evict(self):
    with self._lock:
        fut = q.submit_write("f", 0, arr)
"""
R2_ALLOWED = """
def evict(self):
    with self._lock:
        self.storage.write_rows("f", 0, arr)  # repro: allow[R2]
"""
R2_CLEAN = """
def evict(self):
    with self._lock:
        victim = self._pick()
        fut = q.submit_write("f", 0, arr, wait=False)   # async spill: exempt
    self.storage.write_rows("f", 0, victim)             # outside the lock
"""


def test_r2_flags_blocking_io_under_lock():
    assert _ids(_active(R2_BAD, select=["R2"])) == ["R2"]
    assert _ids(_active(R2_BAD_QUEUE, select=["R2"])) == ["R2"]


def test_r2_suppression_and_clean():
    assert _active(R2_ALLOWED, select=["R2"]) == []
    assert _active(R2_CLEAN, select=["R2"]) == []


# ------------------------------------------------------------------ R3
R3_BAD = """
def gather(self, shape):
    buf = self._rt.pool.acquire(shape, "f32")
    buf[:] = 0
"""
R3_BAD_DISCARD = """
def warm(pool, shape):
    pool.acquire(shape, "f32")
"""
R3_ALLOWED = """
def gather(self, shape):
    buf = self._rt.pool.acquire(shape, "f32")  # repro: allow[R3]
    buf[:] = 0
"""
R3_CLEAN = """
def returned(pool, shape):
    buf = pool.acquire(shape, "f32")
    return buf

def released(pool, shape):
    buf = pool.acquire(shape, "f32")
    try:
        use(buf)
    finally:
        pool.release(buf)

def deferred(pool, shape, dev):
    buf = pool.acquire(shape, "f32")
    pool.defer_release(dev, buf)

def handed_off(pool, q, shape):
    buf = pool.acquire(shape, "f32")
    q.put((7, buf, None))

def wrapped(pool, shape, idx):
    buf = pool.acquire(shape, "f32")
    return Staged(buf, idx)
"""


def test_r3_flags_leaked_pool_buffers():
    fs = _active(R3_BAD, select=["R3"])
    assert _ids(fs) == ["R3"] and fs[0].line == 3
    assert _ids(_active(R3_BAD_DISCARD, select=["R3"])) == ["R3"]


def test_r3_suppression_and_clean():
    assert _active(R3_ALLOWED, select=["R3"]) == []
    assert _active(R3_CLEAN, select=["R3"]) == []


# ------------------------------------------------------------------ R4
R4_BAD = """
def insert(cache, key, arr):
    cache.put(key, arr)

def warm(self, key, loader):
    self.cache.prefetch(key, loader=loader, pin=True)
"""
R4_ALLOWED = """
def insert(cache, key, arr):
    cache.put(key, arr)  # repro: allow[R4] -- test fixture, no budget
"""
R4_CLEAN = """
def insert(cache, key, arr, nb):
    cache.put(key, arr, reserved_bytes=nb)

def warm(self, key, loader, nb):
    self.cache.prefetch(key, loader=loader, pin=True, size_hint=nb)
    self.cache.get(key, loader, size_hint=nb)
    self.cache.prefetch_many([key], loader, True, sizes=[nb])

def lookaside(self, p):
    return self._idx_cache.get(p)     # plain dict, not a HostCache
"""


def test_r4_flags_unreserved_cache_inserts():
    fs = _active(R4_BAD, select=["R4"])
    assert _ids(fs) == ["R4", "R4"]
    assert "reserved_bytes" in fs[0].message
    assert "size_hint" in fs[1].message


def test_r4_suppression_and_clean():
    assert _active(R4_ALLOWED, select=["R4"]) == []
    assert _active(R4_CLEAN, select=["R4"]) == []


# ------------------------------------------------------------------ R5
R5_BAD = """
def f(self):
    self._lock.acquire()
    do_work()
    self._lock.release()
"""
R5_ALLOWED = """
def f(self):
    self._lock.acquire()  # repro: allow[R5]
    do_work()
    self._lock.release()
"""
R5_CLEAN = """
def f(self):
    with self._lock:
        do_work()

def g(self):
    self._lock.acquire()
    try:
        do_work()
    finally:
        self._lock.release()

def pools(self, pool, shape):
    return pool.acquire(shape, "f32")   # BufferPool.acquire, not a lock
"""


def test_r5_flags_bare_lock_acquire():
    fs = _active(R5_BAD, select=["R5"])
    assert _ids(fs) == ["R5"] and fs[0].line == 3


def test_r5_suppression_and_clean():
    assert _active(R5_ALLOWED, select=["R5"]) == []
    assert _active(R5_CLEAN, select=["R5"]) == []


# ------------------------------------------------------------------ R6
R6_BAD = """
import time
def f():
    t0 = time.time()
    return time.time() - t0
"""
R6_ALLOWED = """
import time
def stamp():
    return time.time()  # repro: allow[R6] -- wall-clock manifest timestamp
"""
R6_CLEAN = """
import time
def f():
    t0 = time.perf_counter()
    deadline = time.monotonic() + 5
    return time.perf_counter() - t0
"""


def test_r6_flags_wall_clock():
    assert _ids(_active(R6_BAD, select=["R6"])) == ["R6", "R6"]


def test_r6_suppression_and_clean():
    assert _active(R6_ALLOWED, select=["R6"]) == []
    assert _active(R6_CLEAN, select=["R6"]) == []


# ------------------------------------------------------------------ R7
R7_BAD = """
def stage():
    try:
        work()
    except:
        pass
"""
R7_BAD_SWALLOW = """
def stage():
    for it in items:
        try:
            work(it)
        except Exception:
            continue
"""
R7_ALLOWED = """
def stage():
    try:
        work()
    except Exception:  # repro: allow[R7] -- best-effort cleanup
        pass
"""
R7_CLEAN = """
def stage():
    try:
        work()
    except ValueError:
        pass                      # narrow type: fine
    try:
        work()
    except Exception as e:
        log.warning("stage failed: %s", e)
        raise
    try:
        work()
    except Exception:
        return fallback           # returns a value, not a swallow
"""


def test_r7_flags_swallowed_exceptions():
    assert _ids(_active(R7_BAD, select=["R7"])) == ["R7"]
    assert _ids(_active(R7_BAD_SWALLOW, select=["R7"])) == ["R7"]


def test_r7_suppression_and_clean():
    assert _active(R7_ALLOWED, select=["R7"]) == []
    assert _active(R7_CLEAN, select=["R7"]) == []


# ------------------------------------------------------------------ R8
R8_BAD = """
import threading
def start(run):
    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t
"""
R8_BAD_FROMIMPORT = """
from threading import Thread
def start(run):
    return Thread(target=run)
"""
R8_ALLOWED = """
import threading
def start(run):
    return threading.Thread(target=run)  # repro: allow[R8]
"""
R8_CLEAN = """
from repro.core.threads import join_bounded, spawn
def start(run, counters):
    t = spawn("worker", run)
    join_bounded(t, 5.0, counters)
"""


def test_r8_flags_raw_thread_creation():
    assert _ids(_active(R8_BAD, select=["R8"])) == ["R8"]
    assert _ids(_active(R8_BAD_FROMIMPORT, select=["R8"])) == ["R8"]


def test_r8_suppression_and_clean():
    assert _active(R8_ALLOWED, select=["R8"]) == []
    assert _active(R8_CLEAN, select=["R8"]) == []


# ------------------------------------------------------------------ R9
R9_BAD = """
def wire(self):
    m = self.counters.metrics
    m.counter("ioRetries")
    m.gauge("queue_depth", fn=lambda: 0)
    self.counters.metrics.histogram("Storage.read.Seconds")
"""
R9_ALLOWED = """
def wire(self):
    m = self.counters.metrics
    m.counter("LegacyName")  # repro: allow[R9]
"""
R9_CLEAN = """
def wire(self, tracer):
    m = self.counters.metrics
    m.counter("io.retries")
    m.gauge("storage.io_queue_depth", fn=lambda: 0)
    m.histogram("serve.lookup_seconds")
    self.counters.metrics.gauge("trace.ring_occupancy", fn=lambda: 0.0)
    tracer.counter("cache_bytes", 123)   # Tracer track: 2 positionals
    reg.counter("whatever")              # unknown receiver: not keyed
"""


def test_r9_flags_bad_metric_names():
    assert _ids(_active(R9_BAD, select=["R9"])) == ["R9", "R9", "R9"]


def test_r9_suppression_and_clean():
    assert _active(R9_ALLOWED, select=["R9"]) == []
    assert _active(R9_CLEAN, select=["R9"]) == []


# ----------------------------------------------------------- framework
def test_registry_has_all_nine_rules():
    ids = [r.id for r in all_rules()]
    assert ids == [f"R{i}" for i in range(1, 10)]
    assert all(r.summary for r in all_rules())


def test_previous_line_suppression():
    src = "# repro: allow[R6]\nt = time.time()\n"
    assert _active(src, select=["R6"]) == []
    assert len(_suppressed(src, select=["R6"])) == 1


def test_multi_rule_allow_comment():
    src = "t = time.time()  # repro: allow[R6, R1]\n"
    assert _active(src) == []


def test_suppression_is_per_rule():
    src = "t = time.time()  # repro: allow[R1]\n"  # wrong rule id
    assert _ids(_active(src, select=["R6"])) == ["R6"]


def test_syntax_error_reported_not_raised():
    fs = lint_source("def broken(:\n")
    assert len(fs) == 1 and fs[0].rule == "E0"


def test_json_report_schema():
    doc = json.loads(render_json(lint_source(R1_BAD + R6_ALLOWED), 1, ["x.py"]))
    assert doc["kind"] == "repro-lint" and doc["version"] == 1
    assert [r["id"] for r in doc["rules"]] == [f"R{i}" for i in range(1, 10)]
    assert doc["counts"]["findings"] == len(doc["findings"]) > 0
    assert doc["counts"]["suppressed"] == len(doc["suppressed"]) == 1
    f = doc["findings"][0]
    assert set(f) == {"rule", "path", "line", "col", "message", "suppressed"}


def test_lint_paths_walks_directories(tmp_path):
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "bad.py").write_text("import time\nt = time.time()\n")
    (tmp_path / "pkg" / "ok.py").write_text("x = 1\n")
    fs, _ = split_findings(lint_paths([str(tmp_path)]))
    assert _ids(fs) == ["R6"] and fs[0].path.endswith("bad.py")


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nt = time.time()\n")
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    env_path = str(SRC)

    def run(*args):
        return subprocess.run(
            [sys.executable, "-m", "repro.analysis.lint", *args],
            capture_output=True, text=True, env={"PYTHONPATH": env_path},
        )

    r = run(str(bad), "--format", "json")
    assert r.returncode == 1
    doc = json.loads(r.stdout)
    assert doc["counts"]["findings"] == 1

    r = run(str(ok))
    assert r.returncode == 0
    assert "0 finding(s)" in r.stdout

    out = tmp_path / "LINT_out.json"
    r = run(str(bad), "--format", "json", "--output", str(out))
    assert r.returncode == 1
    assert json.loads(out.read_text())["counts"]["findings"] == 1

    assert run("--list-rules").returncode == 0
    assert run(str(ok), "--select", "R99").returncode == 2


def test_shipped_tree_lints_clean():
    """The CI fast gate runs exactly this: zero unsuppressed findings over
    src/. Any invariant regression in the runtime fails here first."""
    active, suppressed = split_findings(lint_paths([str(SRC)]))
    assert active == [], "\n".join(
        f"{f.path}:{f.line}: {f.rule} {f.message}" for f in active
    )
    # the deliberate allows (wall-clock manifest stamp, sanctioned Thread
    # constructor) stay a short, auditable list
    assert 0 < len(suppressed) < 10
