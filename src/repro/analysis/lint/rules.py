"""Repo-native lint rules R1..R9 for the SSO runtime's invariants.

Every rule here encodes a coordination invariant that an earlier PR fixed by
hand (see ``src/repro/analysis/README.md`` for the catalog with rationale).
The rules are deliberately heuristic — they key on the repo's naming
conventions (``pool``/``cache``/``_lock`` receivers) rather than on type
inference, which keeps them fast, dependency-free, and predictable.  False
positives are handled with ``# repro: allow[Rn]`` at the call site.
"""
from __future__ import annotations

import ast
import re
from typing import Iterator, List, Optional, Set

from repro.analysis.lint.core import Finding, ModuleContext, Rule, register

# Scalar telemetry fields of repro.core.counters.Counters. Kept as a literal
# so the linter never imports runtime code; tests/test_analysis.py asserts
# this set matches dataclasses.fields(Counters) so drift breaks the build.
COUNTERS_SCALAR_FIELDS = frozenset({
    "storage_read_bytes", "storage_write_bytes",
    "storage_read_paged_bytes", "storage_write_paged_bytes",
    "storage_read_ops", "storage_write_ops", "storage_peak_alloc_bytes",
    "h2d_bytes", "d2h_bytes", "host_gather_bytes", "host_scatter_bytes",
    "cache_hits", "cache_misses", "cache_evictions", "cache_bypass",
    "cache_prefetches", "cache_peak_bytes", "pool_trims",
    "pool_release_rejects", "device_flops", "narrow_aggregate_passes",
    "attention_passes", "attention_units", "dense_attention_units",
    "retired_scatter_units", "threads_leaked", "slow_lane_pins",
})

# Blocking storage-tier / I/O-queue entry points (StorageTier + StorageIOQueue
# + inference truncation). submit_write(wait=False) is the sanctioned
# non-blocking under-lock spill and is exempted in R2's check.
BLOCKING_IO_METHODS = frozenset({
    "read_rows", "write_rows", "read_rows_batched", "read_rows_scattered",
    "submit_read", "submit_read_batch", "submit_write", "drain",
    "truncate_rows", "alloc",
})

_LOCKISH_RE = re.compile(r"(^|_)(lock|cond|mutex)$")


def _terminal_name(node: ast.expr) -> Optional[str]:
    """Last path component of a dotted receiver: self._rt.pool -> 'pool'."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _is_lockish(node: ast.expr) -> bool:
    name = _terminal_name(node)
    return bool(name and _LOCKISH_RE.search(name))


def _receiver(call: ast.Call) -> Optional[ast.expr]:
    if isinstance(call.func, ast.Attribute):
        return call.func.value
    return None


def _func_defs(tree: ast.Module):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _enclosing_class_names(tree: ast.Module) -> dict:
    """Map each function/statement node id -> innermost enclosing class name."""
    owner = {}

    def visit(node, cls):
        if isinstance(node, ast.ClassDef):
            cls = node.name
        owner[id(node)] = cls
        for child in ast.iter_child_nodes(node):
            visit(child, cls)

    visit(tree, None)
    return owner


# ------------------------------------------------------------------- R1
@register
class CountersMutationRule(Rule):
    """PR 7 race class: gather workers and the write-behind thread share one
    Counters instance; a bare ``+=`` on its attribute is a lost-update race.
    Mutation is only legal through ``bump()``/``bump_many()`` (or inside the
    Counters class itself, whose methods hold ``self._lock``)."""

    id = "R1"
    name = "counters-unlocked-mutation"
    summary = ("Counters scalar fields must be mutated via bump()/bump_many(),"
               " never by direct attribute assignment")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        owner = _enclosing_class_names(ctx.tree)
        for node in ast.walk(ctx.tree):
            targets: List[ast.expr] = []
            if isinstance(node, ast.AugAssign):
                targets = [node.target]
            elif isinstance(node, ast.Assign):
                targets = node.targets
            else:
                continue
            for t in targets:
                if (
                    isinstance(t, ast.Attribute)
                    and t.attr in COUNTERS_SCALAR_FIELDS
                    and owner.get(id(node)) != "Counters"
                ):
                    op = "+=" if isinstance(node, ast.AugAssign) else "="
                    yield self.finding(
                        ctx, node,
                        f"direct `{_src_attr(t)} {op} ...` mutates Counters "
                        f"field '{t.attr}' without its lock; use "
                        f"counters.bump()/bump_many() [R1]",
                    )


def _src_attr(node: ast.Attribute) -> str:
    base = _terminal_name(node.value)
    return f"{base}.{node.attr}" if base else node.attr


# ------------------------------------------------------------------- R2
@register
class BlockingIOUnderLockRule(Rule):
    """PR 4 deadlock/latency class: a blocking StorageTier/StorageIOQueue
    call inside a ``with <lock>:`` block serializes every cache/pool user
    behind disk latency (and can deadlock against the I/O thread's own
    completion callbacks). Stage the I/O outside the critical section;
    ``submit_write(..., wait=False)`` is the sanctioned under-lock spill."""

    id = "R2"
    name = "blocking-io-under-lock"
    summary = ("no blocking StorageTier/StorageIOQueue call inside a "
               "`with <lock>:` block")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.With):
                continue
            if not any(_is_lockish(item.context_expr) for item in node.items):
                continue
            for call in ast.walk(node):
                if not isinstance(call, ast.Call):
                    continue
                fn = call.func
                if not isinstance(fn, ast.Attribute):
                    continue
                if fn.attr not in BLOCKING_IO_METHODS:
                    continue
                if fn.attr == "submit_write" and _kw_is_false(call, "wait"):
                    continue  # async spill: enqueue only, never blocks
                yield self.finding(
                    ctx, call,
                    f"blocking I/O call `.{fn.attr}(...)` inside a "
                    f"`with <lock>:` block — move it outside the critical "
                    f"section (or use submit_write(wait=False)) [R2]",
                )


def _kw_is_false(call: ast.Call, name: str) -> bool:
    for kw in call.keywords:
        if kw.arg == name and isinstance(kw.value, ast.Constant):
            return kw.value.value is False
    return False


# ------------------------------------------------------------------- R3
@register
class PoolAcquireLeakRule(Rule):
    """PR 8 leak class: a ``pool.acquire(...)`` result that is neither
    released (``release``/``defer_release``/``retire_write``), returned
    (ownership transfer to the caller), nor handed off to another component
    (passed as a call argument, e.g. into a stage queue) leaks a pooled
    buffer on every iteration."""

    id = "R3"
    name = "pool-acquire-leak"
    summary = ("every pool.acquire(...) result must be released, returned, "
               "or handed off on all paths")

    RELEASERS = frozenset({"release", "defer_release", "retire_write"})

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for fn in _func_defs(ctx.tree):
            yield from self._check_fn(ctx, fn)

    def _is_pool_acquire(self, node: ast.AST) -> bool:
        return (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "acquire"
            and (_terminal_name(node.func.value) or "").lstrip("_").endswith("pool")
        )

    def _check_fn(self, ctx: ModuleContext, fn) -> Iterator[Finding]:
        acquires = []  # (assign node, var name) or (expr node, None)
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and self._is_pool_acquire(node.value):
                if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name):
                    acquires.append((node, node.targets[0].id))
                # tuple-unpack acquire isn't an idiom here; ignore
            elif isinstance(node, ast.Expr) and self._is_pool_acquire(node.value):
                yield self.finding(
                    ctx, node,
                    "pool.acquire(...) result discarded — the pooled buffer "
                    "can never be released [R3]",
                )
        for assign, var in acquires:
            if not self._handled(fn, assign, var):
                yield self.finding(
                    ctx, assign,
                    f"pool.acquire(...) into '{var}' is never released, "
                    f"returned, or handed off in this function [R3]",
                )

    def _handled(self, fn, assign, var: str) -> bool:
        after = assign.lineno
        for node in ast.walk(fn):
            if getattr(node, "lineno", 0) < after:
                continue
            if isinstance(node, (ast.Return, ast.Yield, ast.YieldFrom)):
                if node.value is not None and _mentions(node.value, var):
                    return True
            elif isinstance(node, ast.Call):
                if node is assign.value:
                    continue
                fn_attr = node.func.attr if isinstance(node.func, ast.Attribute) else None
                if fn_attr in self.RELEASERS and _mentions_args(node, var):
                    return True
                # hand-off: var passed (bare, or inside a tuple/list literal
                # or a constructor call) to another component. Slices like
                # out=buf[a:b] are scratch use, not ownership transfer.
                if _handed_off(node, var):
                    return True
        return False


def _mentions(node: ast.AST, var: str) -> bool:
    return any(
        isinstance(n, ast.Name) and n.id == var for n in ast.walk(node)
    )


def _mentions_args(call: ast.Call, var: str) -> bool:
    return any(_mentions(a, var) for a in call.args) or any(
        _mentions(k.value, var) for k in call.keywords
    )


def _handed_off(call: ast.Call, var: str) -> bool:
    def bare_names(node) -> Set[str]:
        out: Set[str] = set()
        if isinstance(node, ast.Name):
            out.add(node.id)
        elif isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            for elt in node.elts:
                out |= bare_names(elt)
        elif isinstance(node, ast.Starred):
            out |= bare_names(node.value)
        elif isinstance(node, ast.Call):
            for a in node.args:
                out |= bare_names(a)
            for k in node.keywords:
                out |= bare_names(k.value)
        return out

    for a in call.args:
        if var in bare_names(a):
            return True
    for k in call.keywords:
        if var in bare_names(k.value):
            return True
    return False


# ------------------------------------------------------------------- R4
@register
class ReserveBeforeMaterializeRule(Rule):
    """PR 5 budget class: inserting into the HostCache without reserving the
    bytes first means the array is materialized BEFORE the budget check, so
    peak host memory transiently overshoots the configured cap. ``put`` must
    carry ``reserved_bytes=``; ``get``/``prefetch`` must carry
    ``size_hint=``; ``prefetch_many`` must carry ``sizes=``."""

    id = "R4"
    name = "reserve-before-materialize"
    summary = ("cache put/get/prefetch call sites must pass reserved_bytes= /"
               " size_hint= / sizes=")

    # receiver terminal names treated as a HostCache (exact match, so
    # `_idx_cache` lookaside dicts don't trip the rule)
    CACHE_NAMES = frozenset({"cache", "_cache", "host_cache"})
    # method -> (required keyword, min positional args that also satisfy it)
    REQUIRED = {
        "put": ("reserved_bytes", 7),
        "get": ("size_hint", 3),
        "prefetch": ("size_hint", 4),
        "prefetch_many": ("sizes", 4),
    }

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not isinstance(fn, ast.Attribute) or fn.attr not in self.REQUIRED:
                continue
            recv = _terminal_name(fn.value)
            if recv not in self.CACHE_NAMES:
                continue
            kw, min_pos = self.REQUIRED[fn.attr]
            if any(k.arg == kw for k in node.keywords):
                continue
            if any(k.arg is None for k in node.keywords):  # **kwargs splat
                continue
            if len(node.args) >= min_pos:
                continue
            yield self.finding(
                ctx, node,
                f"`{recv}.{fn.attr}(...)` without `{kw}=` — the cache cannot "
                f"reserve budget before the bytes materialize [R4]",
            )


# ------------------------------------------------------------------- R5
@register
class BareLockAcquireRule(Rule):
    """Bare ``<lock>.acquire()`` outside a try/finally that releases the
    same lock leaks the lock on any exception between acquire and release.
    Use ``with lock:`` (the whole runtime does); the try/finally form is
    tolerated for the rare conditional-acquire pattern."""

    id = "R5"
    name = "bare-lock-acquire"
    summary = "locks are taken via `with`; bare .acquire() needs finally:release"

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        protected = set()
        for trynode in ast.walk(ctx.tree):
            if not isinstance(trynode, ast.Try) or not trynode.finalbody:
                continue
            released = set()
            for n in trynode.finalbody:
                for call in ast.walk(n):
                    if (
                        isinstance(call, ast.Call)
                        and isinstance(call.func, ast.Attribute)
                        and call.func.attr == "release"
                        and _is_lockish(call.func.value)
                    ):
                        released.add(_recv_key(call.func.value))
            if not released:
                continue
            # protected: acquires inside the try body, and in the statement
            # immediately preceding the try (the canonical
            # acquire();try:...finally:release() idiom)
            shields = list(trynode.body)
            prev = _preceding_sibling(ctx.tree, trynode)
            if prev is not None:
                shields.append(prev)
            for n in shields:
                for call in ast.walk(n):
                    if (
                        isinstance(call, ast.Call)
                        and isinstance(call.func, ast.Attribute)
                        and call.func.attr == "acquire"
                        and _recv_key(call.func.value) in released
                    ):
                        protected.add(id(call))
        for node in ast.walk(ctx.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "acquire"
                and _is_lockish(node.func.value)
                and id(node) not in protected
            ):
                yield self.finding(
                    ctx, node,
                    "bare `.acquire()` on a lock without a paired "
                    "finally-release — use `with lock:` [R5]",
                )


def _preceding_sibling(tree: ast.Module, stmt: ast.stmt) -> Optional[ast.stmt]:
    for node in ast.walk(tree):
        for field in ("body", "orelse", "finalbody"):
            seq = getattr(node, field, None)
            if isinstance(seq, list) and stmt in seq:
                i = seq.index(stmt)
                return seq[i - 1] if i > 0 else None
    return None


def _recv_key(node: ast.expr) -> str:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


# ------------------------------------------------------------------- R6
@register
class WallClockLatencyRule(Rule):
    """``time.time()`` is wall clock: NTP slews and DST make it jump, so
    latency/deadline math silently corrupts (the PR-3 bench harness shipped
    with this bug). Use ``time.perf_counter()`` / ``time.monotonic()``;
    genuine wall-clock timestamps (checkpoint manifests) carry an allow."""

    id = "R6"
    name = "wall-clock-latency"
    summary = "no time.time() for latency/deadlines; use perf_counter/monotonic"

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "time"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "time"
            ):
                yield self.finding(
                    ctx, node,
                    "time.time() is wall clock — use time.perf_counter() or "
                    "time.monotonic() for latency/deadline math [R6]",
                )


# ------------------------------------------------------------------- R7
@register
class SwallowedExceptionRule(Rule):
    """A bare ``except:`` (or an ``except Exception:`` whose body only
    ``pass``/``continue``s) inside pipeline code swallows PipelineAbort and
    unwind signals — the fault-injection suite exists precisely because
    unwind must propagate. Handlers that log, re-raise, or return a
    fallback value are fine."""

    id = "R7"
    name = "swallowed-exception"
    summary = "no bare except / silently-swallowed Exception handlers"

    BROAD = frozenset({"Exception", "BaseException"})

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None:
                yield self.finding(
                    ctx, node,
                    "bare `except:` catches SystemExit/KeyboardInterrupt and "
                    "pipeline unwind signals — name the exception [R7]",
                )
                continue
            if (
                isinstance(node.type, ast.Name)
                and node.type.id in self.BROAD
                and all(isinstance(s, (ast.Pass, ast.Continue)) for s in node.body)
            ):
                yield self.finding(
                    ctx, node,
                    f"`except {node.type.id}: pass` silently swallows the "
                    f"error — log it, re-raise, or narrow the type [R7]",
                )


# ------------------------------------------------------------------- R8
@register
class RawThreadRule(Rule):
    """Raw ``threading.Thread(...)`` bypasses the join-bounded lifecycle
    (``repro.core.threads.spawn`` / ``join_bounded``) that guarantees wedged
    workers are timed out, logged, and counted as ``threads_leaked`` instead
    of hanging shutdown. Spawn through the helpers."""

    id = "R8"
    name = "raw-thread-creation"
    summary = "threads only via repro.core.threads.spawn/join_bounded helpers"

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        thread_aliases = {
            local
            for local, full in ctx.from_imports.items()
            if full == "threading.Thread"
        }
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            raw = (
                isinstance(fn, ast.Attribute)
                and fn.attr in ("Thread", "Timer")
                and isinstance(fn.value, ast.Name)
                and fn.value.id == "threading"
            ) or (isinstance(fn, ast.Name) and fn.id in thread_aliases)
            if raw:
                yield self.finding(
                    ctx, node,
                    "raw threading.Thread(...) — use repro.core.threads."
                    "spawn()/join_bounded() so wedged workers are join-"
                    "bounded and counted [R8]",
                )


# ------------------------------------------------------------------- R9
@register
class MetricNameGrammarRule(Rule):
    """Registry metric names feed the Prometheus exporter 1:1
    (``storage.io_queue_depth`` -> ``repro_storage_io_queue_depth``), the
    live sampler's rings, and dashboards that outlive any one run. A name
    outside the ``<subsystem>.<name>`` grammar either collides after
    sanitization or lands in no subsystem group — so it's refused at lint
    time, not discovered on a dashboard. Keyed on the repo's registry
    receivers (``...metrics.counter(...)`` / the local ``m = ...metrics``
    alias); ``Tracer.counter(name, value)`` takes two positionals and is
    not matched."""

    id = "R9"
    name = "metric-name-grammar"
    summary = ("MetricsRegistry names must match <subsystem>.<name> "
               "(lowercase, dot-separated)")

    REGISTRY_RECEIVERS = frozenset({"metrics", "m"})
    METHODS = frozenset({"counter", "gauge", "histogram"})
    GRAMMAR = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not isinstance(fn, ast.Attribute) or fn.attr not in self.METHODS:
                continue
            if _terminal_name(fn.value) not in self.REGISTRY_RECEIVERS:
                continue
            # registry registration takes exactly ONE positional: the name
            # (gauge's fn= is keyword-only here). Tracer.counter(name, value)
            # and other 2-positional calls are a different API.
            if len(node.args) != 1:
                continue
            arg = node.args[0]
            if not isinstance(arg, ast.Constant) or not isinstance(
                arg.value, str
            ):
                continue
            if not self.GRAMMAR.match(arg.value):
                yield self.finding(
                    ctx, node,
                    f"metric name {arg.value!r} violates the "
                    f"<subsystem>.<name> grammar (lowercase segments "
                    f"joined by dots, e.g. 'storage.io_queue_depth') — "
                    f"it would not export/group cleanly [R9]",
                )
