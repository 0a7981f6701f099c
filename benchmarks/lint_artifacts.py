"""Schema lint for CI JSON artifacts (BENCH_*, TRACE_*, LINT_*, LOCKGRAPH_*,
REGRESS_*, and ``*.jsonl`` run ledgers).

Validates that each artifact parses as JSON and carries the keys its
consumers rely on:

- ``BENCH_*`` files: the perf-trajectory payloads written by the benches'
  ``--json`` flags — must be an object with a ``config`` section plus the
  bench's own result section(s), stamped with schema version + config
  fingerprint (benchmarks/common.py).
- ``*.jsonl`` ledgers: one ``repro-run`` record per line
  (repro.obs.ledger) — every line must parse and carry the provenance
  fields, and each record's fingerprint must actually hash its config.
- ``REGRESS_*`` files: the perf-regression sentinel's report
  (benchmarks/regress.py) — checks/counts must be consistent, and an
  uploaded report carrying regressions is flagged (the gate step should
  have failed the job).
- ``TRACE_*`` files: Chrome/Perfetto ``trace_event`` timelines from
  ``--trace`` — must be the object form (``{"traceEvents": [...]}``), every
  event must carry ``name``/``ph``/``ts``/``pid``/``tid`` with a known
  phase, ``"X"`` events need a non-negative ``dur``, and at least one
  non-metadata span must be present (an empty timeline means the tracer was
  never wired through the run — exactly the regression this lint exists to
  catch).
- ``LINT_*`` files: ``repro.analysis.lint --format json`` reports — rule
  catalog + findings/suppressed lists with consistent counts (and since the
  gate step already failed on findings, an uploaded report should be clean).
- ``LOCKGRAPH_*`` files: the dynamic lock-order detector's acquisition
  graph (``repro.analysis.runtime``) — edges/cycles/long-holds plus balance
  counters; zero acquisitions means the instrumentation never engaged.

Run:  python benchmarks/lint_artifacts.py FILE [FILE ...]
Exits nonzero listing every failed check; prints one OK line per file.
"""
import hashlib
import json
import os
import sys

KNOWN_PHASES = {"X", "B", "E", "b", "e", "n", "i", "I", "C", "M", "s", "t",
                "f", "P"}


def lint_trace(path: str, doc) -> list:
    errs = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return [f"{path}: not object-form trace JSON (no traceEvents)"]
    evs = doc["traceEvents"]
    if not isinstance(evs, list):
        return [f"{path}: traceEvents is not a list"]
    spans = 0
    for i, ev in enumerate(evs):
        for key in ("name", "ph", "pid", "tid"):
            if key not in ev:
                errs.append(f"{path}: event {i} missing '{key}'")
                break
        else:
            ph = ev["ph"]
            if ph not in KNOWN_PHASES:
                errs.append(f"{path}: event {i} unknown phase {ph!r}")
            if ph != "M" and "ts" not in ev:
                errs.append(f"{path}: event {i} ({ph}) missing 'ts'")
            if ph == "X":
                if "dur" not in ev or ev["dur"] < 0:
                    errs.append(
                        f"{path}: event {i} ('X') missing/negative 'dur'"
                    )
                spans += 1
        if len(errs) > 20:
            errs.append(f"{path}: ... (truncated)")
            break
    if spans == 0:
        errs.append(f"{path}: no complete ('X') spans — empty timeline")
    return errs


def lint_bench(path: str, doc) -> list:
    errs = []
    if not isinstance(doc, dict):
        return [f"{path}: bench payload is not a JSON object"]
    if "config" not in doc:
        errs.append(f"{path}: missing 'config' section")
    if len(doc) < 2:
        errs.append(f"{path}: no result sections beside 'config'")
    if os.path.basename(path).startswith("BENCH_fault_soak"):
        errs += lint_fault_soak(path, doc)
    return errs


def lint_fault_soak(path: str, doc) -> list:
    """benchmarks/fault_soak.py payload: the fault-tolerance acceptance
    record — the soak section must carry the bit-identity verdict, the
    injected-fault accounting that makes the verdict meaningful (a soak
    that injected nothing proves nothing), and a clean serve lane."""
    errs = []
    cfg = doc.get("config", {})
    for key in ("read_error_rate", "write_error_rate", "depth",
                "gather_workers", "seed", "epochs"):
        if key not in cfg:
            errs.append(f"{path}: config missing '{key}'")
    soak = doc.get("soak")
    if not isinstance(soak, dict):
        return errs + [f"{path}: missing 'soak' result section"]
    if not isinstance(soak.get("identical"), bool):
        errs.append(f"{path}: soak.identical missing/not boolean")
    for key in ("faults_injected", "io_retries", "io_deadline_misses",
                "serve_lookups", "wall_s"):
        if not isinstance(soak.get(key), (int, float)):
            errs.append(f"{path}: soak.{key} missing/not numeric")
    for key in ("losses_clean", "losses_faulty"):
        v = soak.get(key)
        if not isinstance(v, list) or not v:
            errs.append(f"{path}: soak.{key} missing/empty loss trajectory")
    if not isinstance(soak.get("serve_errors"), list):
        errs.append(f"{path}: soak.serve_errors missing/not a list")
    return errs


def lint_lint_report(path: str, doc) -> list:
    """repro.analysis.lint JSON report (LINT_* artifacts)."""
    errs = []
    if not isinstance(doc, dict):
        return [f"{path}: lint report is not a JSON object"]
    if doc.get("version") != 1:
        errs.append(f"{path}: unknown lint schema version {doc.get('version')!r}")
    rules = doc.get("rules")
    if not isinstance(rules, list) or len(rules) < 8:
        errs.append(f"{path}: expected >=8 rules in the catalog")
    elif not all(
        isinstance(r, dict) and r.get("id") and r.get("summary") for r in rules
    ):
        errs.append(f"{path}: rule entries need id+summary")
    counts = doc.get("counts", {})
    for section in ("findings", "suppressed"):
        items = doc.get(section)
        if not isinstance(items, list):
            errs.append(f"{path}: missing '{section}' list")
            continue
        if counts.get(section) != len(items):
            errs.append(f"{path}: counts.{section} != len({section})")
        for i, f in enumerate(items):
            if not all(k in f for k in ("rule", "path", "line", "message")):
                errs.append(f"{path}: {section}[{i}] missing finding keys")
                break
    if doc.get("findings"):
        # the gate step fails the build on findings; an artifact carrying
        # them anyway means the upload ran on a red tree
        errs.append(f"{path}: report carries unsuppressed findings")
    return errs


def lint_lockgraph(path: str, doc) -> list:
    """repro.analysis.runtime lock-acquisition graph (LOCKGRAPH_*)."""
    errs = []
    if not isinstance(doc, dict):
        return [f"{path}: lock graph is not a JSON object"]
    if doc.get("kind") != "repro-lockgraph":
        errs.append(f"{path}: kind != 'repro-lockgraph'")
    if doc.get("version") != 1:
        errs.append(f"{path}: unknown lockgraph version {doc.get('version')!r}")
    for key in ("locks_created", "acquisitions", "releases"):
        if not isinstance(doc.get(key), int):
            errs.append(f"{path}: '{key}' missing/not an int")
    if doc.get("acquisitions") == 0:
        errs.append(f"{path}: zero acquisitions — instrumentation never engaged")
    for key in ("edges", "cycles", "long_holds"):
        if not isinstance(doc.get(key), list):
            errs.append(f"{path}: '{key}' missing/not a list")
    for i, e in enumerate(doc.get("edges") or []):
        if not all(k in e for k in ("held_site", "acquired_site", "count")):
            errs.append(f"{path}: edges[{i}] missing site/count keys")
            break
    if doc.get("cycles"):
        # an uploaded graph with a potential deadlock should have failed
        # the suite; flag it so the artifact can't pass quietly
        errs.append(f"{path}: acquisition graph contains cycles")
    return errs


def _lint_ledger_record(rec) -> list:
    """One ``repro-run`` ledger record (kept standalone: this tool runs
    without PYTHONPATH=src, so the schema is restated here — the authority
    is repro.obs.ledger, whose own validate_record refuses these at write
    time)."""
    errs = []
    if not isinstance(rec, dict):
        return ["record is not a JSON object"]
    if rec.get("kind") != "repro-run":
        errs.append(f"kind != 'repro-run' (got {rec.get('kind')!r})")
    if rec.get("schema_version") != 1:
        errs.append(f"unknown schema_version {rec.get('schema_version')!r}")
    if not isinstance(rec.get("run_kind"), str) or not rec.get("run_kind"):
        errs.append("run_kind missing/empty")
    if not isinstance(rec.get("config"), dict):
        errs.append("config missing/not an object")
    if not isinstance(rec.get("headline"), dict):
        errs.append("headline missing/not an object")
    if not isinstance(rec.get("written_at"), (int, float)):
        errs.append("written_at missing/not numeric")
    fp = rec.get("fingerprint")
    if not isinstance(fp, str) or len(fp) < 8:
        errs.append("fingerprint missing/not a hash string")
    elif isinstance(rec.get("config"), dict):
        blob = json.dumps(rec["config"], sort_keys=True,
                          separators=(",", ":"), default=str)
        if fp != hashlib.sha256(blob.encode()).hexdigest()[:16]:
            errs.append("fingerprint does not hash the config it carries")
    watch = rec.get("watch", {})
    if not isinstance(watch, dict):
        errs.append("watch not an object")
    elif any(d not in ("lower", "higher") for d in watch.values()):
        errs.append("watch directions must be 'lower'/'higher'")
    return errs


def lint_ledger(path: str) -> list:
    """A ``.jsonl`` run ledger: every line a valid repro-run record."""
    errs = []
    n = 0
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError as e:
                errs.append(f"{path}:{i}: unparseable ledger line ({e})")
                continue
            n += 1
            errs += [f"{path}:{i}: {m}" for m in _lint_ledger_record(rec)]
            if len(errs) > 20:
                errs.append(f"{path}: ... (truncated)")
                break
    if n == 0:
        errs.append(f"{path}: empty ledger — producer never appended")
    return errs


def lint_regress(path: str, doc) -> list:
    """benchmarks/regress.py sentinel report (REGRESS_* artifacts)."""
    errs = []
    if not isinstance(doc, dict):
        return [f"{path}: regress report is not a JSON object"]
    if doc.get("kind") != "repro-regress":
        errs.append(f"{path}: kind != 'repro-regress'")
    if doc.get("version") != 1:
        errs.append(
            f"{path}: unknown regress schema version {doc.get('version')!r}"
        )
    if not isinstance(doc.get("ledger"), str):
        errs.append(f"{path}: 'ledger' path missing")
    checks = doc.get("checks")
    if not isinstance(checks, list):
        return errs + [f"{path}: missing 'checks' list"]
    verdicts = {"ok": 0, "regression": 0, "skip": 0}
    for i, c in enumerate(checks):
        if not all(k in c for k in ("run_kind", "metric", "verdict")):
            errs.append(f"{path}: checks[{i}] missing run_kind/metric/verdict")
            continue
        v = c["verdict"]
        if v not in verdicts:
            errs.append(f"{path}: checks[{i}] unknown verdict {v!r}")
        else:
            verdicts[v] += 1
    counts = doc.get("counts", {})
    expected = dict(checks=len(checks), regressions=verdicts["regression"],
                    ok=verdicts["ok"], skipped=verdicts["skip"])
    if counts != expected:
        errs.append(f"{path}: counts {counts} != recomputed {expected}")
    if verdicts["regression"]:
        # the sentinel gate exits nonzero on regressions; an uploaded
        # report carrying them means the upload ran on a red job
        errs.append(f"{path}: report carries regressions")
    return errs


def lint(path: str) -> list:
    if not os.path.exists(path):
        return [f"{path}: file not found"]
    if path.endswith(".jsonl"):
        # JSON Lines ledgers can't go through the whole-file json.load
        return lint_ledger(path)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: not valid JSON ({e})"]
    # content-sniff first (traces and the analysis payloads are
    # unambiguous), filename prefix second — so arbitrarily named outputs
    # still lint as the right kind
    if isinstance(doc, dict) and "traceEvents" in doc:
        return lint_trace(path, doc)
    if isinstance(doc, dict) and doc.get("kind") == "repro-lint":
        return lint_lint_report(path, doc)
    if isinstance(doc, dict) and doc.get("kind") == "repro-lockgraph":
        return lint_lockgraph(path, doc)
    if isinstance(doc, dict) and doc.get("kind") == "repro-regress":
        return lint_regress(path, doc)
    if isinstance(doc, dict) and doc.get("kind") == "repro-run":
        return [f"{path}: {m}" for m in _lint_ledger_record(doc)]
    base = os.path.basename(path)
    if base.startswith("TRACE"):
        return lint_trace(path, doc)
    if base.startswith("LINT_"):
        return lint_lint_report(path, doc)
    if base.startswith("LOCKGRAPH"):
        return lint_lockgraph(path, doc)
    if base.startswith("REGRESS"):
        return lint_regress(path, doc)
    return lint_bench(path, doc)


def main(argv) -> int:
    if not argv:
        print("usage: lint_artifacts.py FILE [FILE ...]", file=sys.stderr)
        return 2
    failed = False
    for path in argv:
        errs = lint(path)
        if errs:
            failed = True
            for e in errs:
                print(f"FAIL {e}", file=sys.stderr)
        else:
            print(f"OK   {path}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
