"""Run ledger + regression sentinel tests.

Load-bearing properties:

- ledger records round-trip (append -> records/latest/series) and the
  config fingerprint is stable under key order but sensitive to values;
- records missing provenance fields are REFUSED (``LedgerSchemaError``),
  never appended — the ledger cannot accumulate unattributable lines;
- two threads appending concurrently interleave whole lines, never torn
  ones (every line parses and validates afterwards);
- sentinel statistics: a 30% step regression on a quiet baseline is caught
  immediately, 200 seeded gaussian-noise trials produce ZERO false
  positives at the default band, and fewer than ``min_samples`` baselines
  yields a skip verdict, not a judgement;
- ``benchmarks/regress.py`` end-to-end (subprocess): exit 0 on a clean
  fixture ledger, exit 1 + FAIL line on one with an injected 30% wall_s
  regression, exit 0 on a missing ledger (cold start).
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro.obs.ledger import (
    LEDGER_KIND, LEDGER_SCHEMA_VERSION, LedgerSchemaError, RunLedger,
    config_fingerprint, make_record, resolve_path, validate_record,
)
from repro.obs.regress import (
    OK, REGRESSION, SKIP, check_ledger, check_series, mad_sigma, median,
    report_payload,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _record(config, headline, run_kind="bench_x", **kw):
    kw.setdefault("watch", {k: "lower" for k in headline})
    return make_record(run_kind, config, headline, **kw)


# ------------------------------------------------------------- fingerprints
def test_fingerprint_stable_and_value_sensitive():
    a = config_fingerprint({"nodes": 4000, "depth": 2})
    b = config_fingerprint({"depth": 2, "nodes": 4000})   # key order
    c = config_fingerprint({"nodes": 4001, "depth": 2})
    assert a == b
    assert a != c
    assert len(a) == 16 and int(a, 16) >= 0   # short hex hash


def test_make_record_carries_provenance_and_counters():
    from repro.core import Counters

    c = Counters()
    c.bump("cache_hits", 7)
    c.record_busy("gather", 0.25)
    rec = _record({"n": 1}, {"wall_s": 2.0}, counters=c, backend="cpu")
    assert rec["kind"] == LEDGER_KIND
    assert rec["schema_version"] == LEDGER_SCHEMA_VERSION
    assert rec["fingerprint"] == config_fingerprint({"n": 1})
    assert rec["backend"] == "cpu"
    assert rec["counters"]["cache_hits"] == 7
    assert rec["counters"]["busy_gather"] == pytest.approx(0.25)
    assert isinstance(rec["metrics"], dict)    # registry snapshot rode along
    assert validate_record(rec) == []


def test_ledger_roundtrip_latest_series(tmp_path):
    led = RunLedger(str(tmp_path / "runs" / "ledger.jsonl"))  # parent mkdir
    for i, wall in enumerate((1.0, 1.1, 0.9)):
        led.append(_record({"n": 1}, {"wall_s": wall, "step": i}))
    led.append(_record({"n": 1}, {"qps": 50.0}, run_kind="bench_y"))
    assert led.run_kinds() == ["bench_x", "bench_y"]
    assert len(led.records()) == 4
    assert led.latest("bench_x")["headline"]["wall_s"] == pytest.approx(0.9)
    assert led.series("bench_x", "wall_s") == [1.0, 1.1, 0.9]
    # dotted and bare paths are the same query for headline metrics
    assert led.series("bench_x", "headline.wall_s") == [1.0, 1.1, 0.9]
    assert led.latest("missing_kind") is None
    assert led.series("bench_x", "no_such_metric") == []


def test_series_fingerprint_filter(tmp_path):
    led = RunLedger(str(tmp_path / "ledger.jsonl"))
    for wall in (1.0, 2.0):
        led.append(_record({"n": 1}, {"wall_s": wall}))
    led.append(_record({"n": 2}, {"wall_s": 99.0}))   # other config
    fp = config_fingerprint({"n": 1})
    assert led.series("bench_x", "wall_s", fingerprint=fp) == [1.0, 2.0]
    assert led.series("bench_x", "wall_s") == [1.0, 2.0, 99.0]


def test_resolve_path_walks_nested_and_defaults_to_headline():
    rec = _record({"n": 1}, {"wall_s": 3.0}, extra={"soak": {"faults": 5}})
    assert resolve_path(rec, "wall_s") == 3.0
    assert resolve_path(rec, "headline.wall_s") == 3.0
    assert resolve_path(rec, "soak.faults") == 5
    assert resolve_path(rec, "soak.nope") is None


# ----------------------------------------------------------------- refusals
def test_append_refuses_unattributable_records(tmp_path):
    led = RunLedger(str(tmp_path / "ledger.jsonl"))
    good = _record({"n": 1}, {"wall_s": 1.0})
    for strip in ("fingerprint", "config", "headline", "run_kind",
                  "written_at"):
        bad = {k: v for k, v in good.items() if k != strip}
        with pytest.raises(LedgerSchemaError, match=strip):
            led.append(bad)
    # fingerprint must actually hash the config it rides with
    forged = dict(good, config={"n": 2})
    with pytest.raises(LedgerSchemaError, match="does not match"):
        led.append(forged)
    with pytest.raises(LedgerSchemaError, match="lower/higher"):
        led.append(dict(good, watch={"wall_s": "sideways"}))
    assert not os.path.exists(led.path)   # nothing was ever written


def test_records_raise_on_torn_line(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    led = RunLedger(path)
    led.append(_record({"n": 1}, {"wall_s": 1.0}))
    with open(path, "a") as f:
        f.write('{"kind": "repro-run", "truncat\n')
    with pytest.raises(LedgerSchemaError, match=":2:"):
        led.records()


# -------------------------------------------------------------- concurrency
def test_two_thread_append_no_torn_lines(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    led = RunLedger(path)
    n_per_thread = 100

    def writer(tid):
        for i in range(n_per_thread):
            led.append(_record(
                {"n": 1}, {"wall_s": 1.0, "tid": tid, "i": i},
            ))

    threads = [threading.Thread(target=writer, args=(t,)) for t in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = led.records()   # raises on any torn line
    assert len(recs) == 2 * n_per_thread
    for rec in recs:
        assert validate_record(rec) == []
    # every (tid, i) pair landed exactly once
    seen = {(r["headline"]["tid"], r["headline"]["i"]) for r in recs}
    assert len(seen) == 2 * n_per_thread


# --------------------------------------------------------- sentinel: series
def test_step_regression_detected_both_directions():
    rng = np.random.default_rng(0)
    base = list(1.0 + 0.02 * rng.standard_normal(20))
    r = check_series(base, 1.30, direction="lower")
    assert r.verdict == REGRESSION
    assert "+3" in r.detail or "+2" in r.detail    # ~+30% vs median
    assert check_series(base, 1.02, direction="lower").verdict == OK
    # higher-is-better metric (qps): a 30% DROP is the regression
    base_hi = list(100.0 + 2.0 * rng.standard_normal(20))
    assert check_series(base_hi, 70.0, direction="higher").verdict \
        == REGRESSION
    assert check_series(base_hi, 99.0, direction="higher").verdict == OK


def test_noise_only_series_no_false_positive_200_trials():
    rng = np.random.default_rng(42)
    for _ in range(200):
        base = list(1.0 + 0.02 * rng.standard_normal(20))
        cur = float(1.0 + 0.02 * rng.standard_normal())
        r = check_series(base, cur, direction="lower")
        assert r.verdict == OK, (
            f"false positive on pure noise: {r.detail}"
        )


def test_min_samples_guard_skips():
    r = check_series([1.0, 1.1], 9.9, min_samples=3)
    assert r.verdict == SKIP
    assert r.n_baseline == 2
    assert "min_samples" in r.detail
    assert check_series([1.0, 1.1, 1.0], 9.9, min_samples=3).verdict \
        == REGRESSION


def test_zero_variance_baseline_uses_rel_floor():
    base = [5.0] * 10                    # MAD = 0: band = rel_floor * 5
    assert check_series(base, 5.2).verdict == OK        # +4% < 10% floor
    assert check_series(base, 5.6).verdict == REGRESSION   # +12%


def test_check_series_rejects_bad_direction():
    with pytest.raises(ValueError, match="direction"):
        check_series([1.0] * 5, 1.0, direction="sideways")


def test_median_and_mad_sigma_consistency():
    assert median([]) == 0.0
    assert median([3.0, 1.0, 2.0]) == 2.0
    assert median([4.0, 1.0, 2.0, 3.0]) == 2.5
    # MAD sigma is consistent with stddev on gaussian data
    rng = np.random.default_rng(7)
    xs = list(10.0 + 3.0 * rng.standard_normal(4001))
    assert mad_sigma(xs) == pytest.approx(3.0, rel=0.10)
    assert mad_sigma([5.0] * 9 + [500.0]) == 0.0   # one outlier: robust


# --------------------------------------------------------- sentinel: ledger
def _seed_ledger(path, walls, config=None, run_kind="bench_x"):
    led = RunLedger(path)
    for w in walls:
        led.append(_record(
            config or {"n": 1}, {"wall_s": w}, run_kind=run_kind,
        ))
    return led


def test_check_ledger_flags_latest_regression(tmp_path):
    led = _seed_ledger(str(tmp_path / "l.jsonl"),
                       [1.0, 1.02, 0.98, 1.01, 1.35])
    (r,) = check_ledger(led)
    assert (r.run_kind, r.metric) == ("bench_x", "wall_s")
    assert r.verdict == REGRESSION
    assert r.n_baseline == 4


def test_check_ledger_baseline_excludes_other_fingerprints(tmp_path):
    led = _seed_ledger(str(tmp_path / "l.jsonl"), [1.0, 1.0, 1.0])
    # a different config's fast runs must not poison this config's baseline
    for w in (0.1, 0.1, 0.1):
        led.append(_record({"n": 99}, {"wall_s": w}))
    led.append(_record({"n": 1}, {"wall_s": 1.01}))
    (r,) = check_ledger(led)
    assert r.verdict == OK
    assert r.n_baseline == 3                 # only the {"n": 1} records


def test_check_ledger_skips_unwatched_and_missing_metrics(tmp_path):
    led = RunLedger(str(tmp_path / "l.jsonl"))
    led.append(make_record("quiet", {"n": 1}, {"wall_s": 1.0}))   # no watch
    led.append(_record({"n": 1}, {"wall_s": 1.0},
                       watch={"qps": "higher"}))   # watched metric absent
    results = check_ledger(led)
    assert [r.verdict for r in results] == [SKIP, SKIP]


def test_report_payload_counts(tmp_path):
    led = _seed_ledger(str(tmp_path / "l.jsonl"),
                       [1.0, 1.0, 1.0, 1.0, 1.5])
    results = check_ledger(led)
    payload = report_payload(results, led.path, {"window": 20})
    assert payload["kind"] == "repro-regress"
    assert payload["version"] == 1
    assert payload["counts"] == {
        "checks": 1, "regressions": 1, "ok": 0, "skipped": 0,
    }
    assert payload["checks"][0]["metric"] == "wall_s"
    json.dumps(payload)   # artifact must be JSON-serializable as-is


# ------------------------------------------------------ sentinel: CLI (e2e)
def _run_sentinel(tmp_path, *argv):
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "benchmarks", "regress.py"),
         *argv],
        capture_output=True, text=True, cwd=str(tmp_path), timeout=60,
    )


def test_regress_cli_ok_on_clean_ledger(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    _seed_ledger(path, [1.0, 1.01, 0.99, 1.0, 1.02])
    report = str(tmp_path / "REGRESS_report.json")
    p = _run_sentinel(tmp_path, "--ledger", path, "--json", report)
    assert p.returncode == 0, p.stderr
    assert "ok,bench_x.wall_s" in p.stdout
    with open(report) as f:
        doc = json.load(f)
    assert doc["counts"]["regressions"] == 0


def test_regress_cli_fails_on_injected_regression(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    _seed_ledger(path, [1.0, 1.01, 0.99, 1.0, 1.30])   # +30% step
    report = str(tmp_path / "REGRESS_report.json")
    p = _run_sentinel(tmp_path, "--ledger", path, "--json", report)
    assert p.returncode == 1
    assert "regression,bench_x.wall_s" in p.stdout
    assert "FAIL bench_x.wall_s" in p.stderr
    with open(report) as f:
        assert json.load(f)["counts"]["regressions"] == 1


def test_regress_cli_cold_start_is_not_a_failure(tmp_path):
    p = _run_sentinel(tmp_path, "--ledger",
                      str(tmp_path / "missing.jsonl"))
    assert p.returncode == 0
    assert "cold start" in p.stdout


def test_regress_cli_min_samples_skip(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    _seed_ledger(path, [1.0, 1.30])   # 1 baseline sample: skip, even at +30%
    p = _run_sentinel(tmp_path, "--ledger", path)
    assert p.returncode == 0
    assert "skip,bench_x.wall_s" in p.stdout
