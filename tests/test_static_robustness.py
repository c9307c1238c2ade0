"""Static scans over idc_models_tpu/: no silent failure swallowing, no
stray `print(` output.

A bare ``except:`` (catches KeyboardInterrupt/SystemExit too) or an
``except Exception: pass``-style handler whose body discards the error
turns every future bug at that site into silent corruption — the exact
failure class this PR's robustness layer exists to eliminate. This test
walks the package AST and fails on any new one outside the explicit
allowlist, so silent-failure handlers cannot regress in through review.

Likewise for output (ISSUE 5): the observability layer routes run
output through `observe.JsonlLogger`, the span tracer, and the metrics
registry — a bare ``print(`` in library code is invisible to every one
of those. The print scan bans new ones outside the documented
allowlist (reference-parity prints like the Timer line, and the CLI,
whose epilogues ARE the product surface).

Allowlisted sites must be best-effort / user-facing BY DESIGN — each
entry documents why.
"""

import ast
from pathlib import Path

PACKAGE = Path(__file__).parent.parent / "idc_models_tpu"

# (relative path, enclosing function) -> why swallowing is correct there
ALLOWLIST = {
    ("observe/logging.py", "_jsonable"):
        "best-effort scalar coercion; falls through to the array/repr "
        "paths below — the record is still written",
    ("serve/scheduler.py", "_abort_running"):
        "engine-failure cleanup: release() may fail on the already-"
        "broken engine, but every slot must still be marked failed "
        "while the ORIGINAL engine error propagates to the caller",
}

# (relative path, enclosing function) -> why a print is correct there.
# A file mapped to "*" allowlists every function in it.
PRINT_ALLOWLIST = {
    ("cli.py", "*"):
        "the CLI's stdout/stderr epilogues ARE its product surface "
        "(summary lines, usage errors, progress) — the reference's "
        "scripts print the same way; structured copies go through the "
        "jsonl logger alongside",
    ("observe/timer.py", "__exit__"):
        "the reference-parity '{name} took {t} seconds' line (SURVEY.md "
        "C17) — byte-for-byte print parity is the contract",
    ("train/loop.py", "fit"):
        "Keras-`fit`-style per-epoch progress + resume notice, the "
        "reference's model.fit console behavior; the jsonl logger "
        "carries the structured copy",
    ("train/loop.py", "two_phase_fit"):
        "reference-parity console output (initial floor, raw history "
        "dicts — dist_model_tf_vgg.py:100-101,131-132) plus the "
        "feature-cache fallback notice",
    ("federated/driver.py", "run_rounds"):
        "opt-in (verbose=True) stderr healing notice while the round "
        "retries — the structured record goes to round_health",
    ("models/pretrained.py", "maybe_load_pretrained"):
        "load confirmation the CLI tests key on ('loaded pretrained "
        "weights'); mismatches go through warnings.warn",
}

_BROAD = {"Exception", "BaseException"}


def _enclosing_function(stack):
    for node in reversed(stack):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return node.name
    return "<module>"


def _is_swallowing(handler: ast.ExceptHandler) -> bool:
    """Body is only pass/continue/constant-expressions (docstrings,
    Ellipsis): the caught error influences nothing."""
    return all(
        isinstance(n, (ast.Pass, ast.Continue))
        or (isinstance(n, ast.Expr) and isinstance(n.value, ast.Constant))
        for n in handler.body)


def _catches_broadly(handler: ast.ExceptHandler) -> bool:
    if handler.type is None:
        return True
    types = (handler.type.elts if isinstance(handler.type, ast.Tuple)
             else [handler.type])
    return any(isinstance(t, ast.Name) and t.id in _BROAD for t in types)


def _scan(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    rel = str(path.relative_to(PACKAGE)).replace("\\", "/")
    violations = []

    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ExceptHandler):
                bare = child.type is None
                swallowing = (_catches_broadly(child)
                              and _is_swallowing(child))
                if bare or swallowing:
                    key = (rel, _enclosing_function(stack))
                    if bare or key not in ALLOWLIST:
                        violations.append(
                            (rel, child.lineno,
                             "bare except" if bare
                             else "except Exception: pass",
                             _enclosing_function(stack)))
            walk(child, stack + [child])

    walk(tree, [])
    return violations


def test_no_silent_exception_swallowing():
    files = sorted(PACKAGE.rglob("*.py"))
    assert files, f"package not found at {PACKAGE}"
    violations = []
    for f in files:
        violations.extend(_scan(f))
    assert not violations, (
        "silent failure handlers found (add real handling, narrow the "
        "exception type, or — only for genuinely best-effort sites — "
        f"extend the documented ALLOWLIST): {violations}")


def _scan_prints(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    rel = str(path.relative_to(PACKAGE)).replace("\\", "/")
    if (rel, "*") in PRINT_ALLOWLIST:
        return [], set()
    violations, live = [], set()

    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            if (isinstance(child, ast.Call)
                    and isinstance(child.func, ast.Name)
                    and child.func.id == "print"):
                key = (rel, _enclosing_function(stack))
                live.add(key)
                if key not in PRINT_ALLOWLIST:
                    violations.append((rel, child.lineno, key[1]))
            walk(child, stack + [child])

    walk(tree, [])
    return violations, live


def test_no_bare_prints():
    """Library output goes through the logger / tracer / registry
    (observe/), not print — a print is invisible to every export path
    and unconditionally spams embedding applications. The documented
    allowlist holds the reference-parity prints and the CLI."""
    violations, live = [], set()
    for f in sorted(PACKAGE.rglob("*.py")):
        v, l = _scan_prints(f)
        violations.extend(v)
        live.update(l)
    assert not violations, (
        "bare print( in library code (route it through "
        "observe.JsonlLogger / trace spans / the metrics registry, or "
        "— only for genuinely user-facing reference-parity output — "
        f"extend the documented PRINT_ALLOWLIST): {violations}")


def test_print_allowlist_entries_still_exist():
    """A stale print-allowlist entry means the site was fixed or moved
    — prune it so the list stays an honest inventory."""
    live = set()
    for f in sorted(PACKAGE.rglob("*.py")):
        _, l = _scan_prints(f)
        live.update(l)
    whole_file = {rel for rel, fn in PRINT_ALLOWLIST if fn == "*"}
    present_files = {
        str(f.relative_to(PACKAGE)).replace("\\", "/")
        for f in PACKAGE.rglob("*.py")}
    stale = {(rel, fn) for rel, fn in PRINT_ALLOWLIST
             if fn != "*" and (rel, fn) not in live}
    stale |= {(rel, "*") for rel in whole_file
              if rel not in present_files}
    assert not stale, f"print allowlist entries match no code: {stale}"


# -- metrics hygiene (ISSUE 7 satellites) -----------------------------------
#
# 1. Every metrics-registry registration must carry non-empty help text:
#    the /metrics exposition renders `# HELP` from it, and a bare metric
#    name is exactly the kind of operational surface that rots into
#    "nobody knows what this counts".
# 2. `time.time()` is banned in serve/ + observe/ outside a documented
#    wall-clock-anchor allowlist: hot-path intervals must come from
#    time.monotonic()/perf_counter (wall time jumps under NTP slew and
#    breaks durations); wall clocks are for ANCHORING records to epoch
#    time, which each allowlisted site documents.

_METRIC_FACTORIES = {"counter", "gauge", "histogram"}

# (relative path, enclosing function) -> why wall-clock is correct there
TIME_TIME_ALLOWLIST = {
    ("observe/logging.py", "log"):
        "every jsonl record's `ts` anchor — the cross-run comparison "
        "axis; never used for durations",
    ("observe/trace.py", "__init__"):
        "the tracer's one wall anchor (wall_t0) mapping monotonic span "
        "offsets to epoch time; durations stay on the injected "
        "monotonic clock",
    ("observe/metrics_registry.py", "write_snapshot"):
        "the standalone snapshot file's header timestamp",
}


def _scan_metric_help(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    rel = str(path.relative_to(PACKAGE)).replace("\\", "/")
    violations = []

    def walk(node):
        for child in ast.iter_child_nodes(node):
            if (isinstance(child, ast.Call)
                    and isinstance(child.func, ast.Attribute)
                    and child.func.attr in _METRIC_FACTORIES
                    and child.args
                    and isinstance(child.args[0], ast.Constant)
                    and isinstance(child.args[0].value, str)):
                help_node = None
                if len(child.args) > 1:
                    help_node = child.args[1]
                else:
                    for kw in child.keywords:
                        if kw.arg == "help":
                            help_node = kw.value
                ok = (isinstance(help_node, ast.Constant)
                      and isinstance(help_node.value, str)
                      and help_node.value.strip())
                if not ok:
                    violations.append(
                        (rel, child.lineno, child.args[0].value))
            walk(child)

    walk(tree)
    return violations


def test_metric_registrations_carry_help_text():
    violations = []
    for f in sorted(PACKAGE.rglob("*.py")):
        if f.name == "metrics_registry.py":
            continue      # the factory definitions, not registrations
        violations.extend(_scan_metric_help(f))
    assert not violations, (
        "metrics registered without help text (the /metrics exposition "
        "renders '# HELP' from it — every instrument must say what it "
        f"counts): {violations}")


def _scan_time_time(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    rel = str(path.relative_to(PACKAGE)).replace("\\", "/")
    violations, live = [], set()

    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            if (isinstance(child, ast.Call)
                    and isinstance(child.func, ast.Attribute)
                    and child.func.attr == "time"
                    and isinstance(child.func.value, ast.Name)
                    and child.func.value.id == "time"):
                key = (rel, _enclosing_function(stack))
                live.add(key)
                if key not in TIME_TIME_ALLOWLIST:
                    violations.append((rel, child.lineno, key[1]))
            walk(child, stack + [child])

    walk(tree, [])
    return violations, live


def test_no_wall_clock_in_serve_observe_hot_paths():
    violations, live = [], set()
    for sub in ("serve", "observe"):
        for f in sorted((PACKAGE / sub).rglob("*.py")):
            v, l = _scan_time_time(f)
            violations.extend(v)
            live.update(l)
    assert not violations, (
        "time.time() in serve/ or observe/ outside the documented "
        "wall-clock-anchor allowlist (durations and deadlines use "
        "time.monotonic()/perf_counter — wall time jumps under NTP "
        f"slew; extend TIME_TIME_ALLOWLIST only for record anchors): "
        f"{violations}")
    stale = set(TIME_TIME_ALLOWLIST) - live
    assert not stale, (
        f"time.time allowlist entries match no code: {stale}")


def test_allowlist_entries_still_exist():
    """A stale allowlist entry means the site was fixed or moved —
    prune it so the list stays an honest inventory."""
    live = set()
    for f in sorted(PACKAGE.rglob("*.py")):
        rel = str(f.relative_to(PACKAGE)).replace("\\", "/")
        tree = ast.parse(f.read_text(), filename=str(f))

        def walk(node, stack):
            for child in ast.iter_child_nodes(node):
                if (isinstance(child, ast.ExceptHandler)
                        and _catches_broadly(child)
                        and _is_swallowing(child)):
                    live.add((rel, _enclosing_function(stack)))
                walk(child, stack + [child])

        walk(tree, [])
    stale = set(ALLOWLIST) - live
    assert not stale, f"allowlist entries no longer match any code: {stale}"


# -- serve/ per-slot exception discipline (ISSUE 8 satellite) ---------------
#
# The resilience layer's whole contract is that a fault is either
# RECOVERED (the entry is quarantined/retried/finished honestly) or
# PROPAGATED (the engine-failure cleanup aborts the batch and the error
# re-raises). An except block in serve/ that does neither — catches,
# logs-or-not, and falls through — is a request silently lost, the
# exact bug class the quarantine machinery exists to kill. This scan
# walks every handler in serve/ — serve/cluster/ included (ISSUE 12),
# which now also covers the elastic layer's autoscaler and the
# persistent compile cache (ISSUE 18): the router's handlers must
# route through ITS recovery entry point, `_fail_replica` (mark the
# replica dead + migrate its journal), the cluster-scope analogue of
# the scheduler's quarantine — and requires a `raise` or a call to one
# of the recovery entry points in the handler body, outside the
# documented allowlist.

_SERVE_RECOVERY_CALLS = {"_quarantine", "_abort_running",
                         "_fail_replica"}

# (path relative to serve/, enclosing function) -> why neither raising
# nor quarantining is correct there
SERVE_EXCEPT_ALLOWLIST = {
    ("scheduler.py", "_abort_running"):
        "the cleanup itself: release() may fail on the already-broken "
        "engine, but every in-flight slot must still be marked failed "
        "while the ORIGINAL engine error propagates to the caller",
    ("api.py", "resubmit_pending"):
        "journal recovery's documented skip: a WAL entry the rebuilt "
        "server can never serve (decommissioned tenant, shrunken "
        "t_max) is warned about and LEFT IN THE WAL for a rerun — "
        "aborting would block every other tenant's recovery",
    ("compile_cache.py", "load"):
        "the cache's best-effort contract (ISSUE 18): a blob that "
        "exists but cannot deserialize (torn write that survived a "
        "crash, foreign-toolchain collision) is EVICTED, counted as "
        "evicted_corrupt, logged, and reported as a miss — spin-up "
        "must fall back to a real compile, never die on a bad cache "
        "entry; tests/test_elastic.py pins the evict-as-miss path",
}


def _handler_recovers(handler: ast.ExceptHandler) -> bool:
    """True when the handler body re-raises (any Raise, including a
    translated one) or calls a recovery entry point."""
    for node in ast.walk(ast.Module(body=handler.body,
                                    type_ignores=[])):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call):
            name = (node.func.attr if isinstance(node.func,
                                                 ast.Attribute)
                    else node.func.id if isinstance(node.func, ast.Name)
                    else None)
            if name in _SERVE_RECOVERY_CALLS:
                return True
    return False


def _scan_serve_handlers(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    rel = str(path.relative_to(PACKAGE / "serve")).replace("\\", "/")
    violations, live = [], set()

    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ExceptHandler):
                key = (rel, _enclosing_function(stack))
                if not _handler_recovers(child):
                    live.add(key)
                    if key not in SERVE_EXCEPT_ALLOWLIST:
                        violations.append((rel, child.lineno, key[1]))
            walk(child, stack + [child])

    walk(tree, [])
    return violations, live


# -- single cost-extraction point (ISSUE 9 satellite) -----------------------
#
# XLA cost/memory accounting goes through ONE normalizing extraction
# point — observe.profile.program_report — which handles the backend
# quirks (list-vs-dict cost_analysis returns, absent memory_analysis)
# and degrades loudly-but-gracefully. Before PR 9 the parsing was
# copy-pasted across two experiments files and a test; this scan keeps
# the invariant from regressing: a direct `.cost_analysis()` /
# `.memory_analysis()` attribute call anywhere in the repo's python
# (package, experiments/, tests/) outside the documented allowlist
# fails.

REPO = Path(__file__).parent.parent

_XLA_ANALYSIS_CALLS = {"cost_analysis", "memory_analysis"}

# (path relative to the repo root, enclosing function) -> why a direct
# call is correct there
COST_ANALYSIS_ALLOWLIST = {
    ("idc_models_tpu/observe/profile.py", "program_report"):
        "THE extraction point: the one site allowed to touch the raw "
        "XLA analyses, normalizing their quirks for everyone else",
}


def _scan_xla_analysis_calls(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    rel = str(path.relative_to(REPO)).replace("\\", "/")
    violations, live = [], set()

    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            if (isinstance(child, ast.Call)
                    and isinstance(child.func, ast.Attribute)
                    and child.func.attr in _XLA_ANALYSIS_CALLS):
                key = (rel, _enclosing_function(stack))
                live.add(key)
                if key not in COST_ANALYSIS_ALLOWLIST:
                    violations.append((rel, child.lineno,
                                       child.func.attr, key[1]))
            walk(child, stack + [child])

    walk(tree, [])
    return violations, live


def _xla_analysis_files():
    me = Path(__file__).resolve()
    return [f for sub in ("idc_models_tpu", "experiments", "tests")
            for f in sorted((REPO / sub).rglob("*.py"))
            if f.resolve() != me]


def test_single_cost_analysis_extraction_point():
    violations, live = [], set()
    for f in _xla_analysis_files():
        v, l = _scan_xla_analysis_calls(f)
        violations.extend(v)
        live.update(l)
    assert not violations, (
        "direct .cost_analysis()/.memory_analysis() calls outside "
        "observe.profile.program_report (route through "
        "program_report/register_program — it normalizes backend "
        "quirks and keeps the accounting schema in one place; extend "
        "the documented COST_ANALYSIS_ALLOWLIST only for the "
        f"extraction point itself): {violations}")
    stale = set(COST_ANALYSIS_ALLOWLIST) - live
    assert not stale, (
        f"cost-analysis allowlist entries match no code: {stale}")


# ---------------------------------------------------------------------------
# ISSUE 16: DenseNet stays concat-free — `concatenate` is banned in
# models/densenet.py outside the documented parity reference. The packed
# dense blocks exist precisely because the iterated concat re-reads and
# re-writes the whole growing feature map every layer (the PR 14 MFU
# attribution measured intensity 2.0 against a ~240 ridge); a concat
# quietly reintroduced anywhere else in the model would silently undo
# the data-movement fix while every numeric test keeps passing.
# ---------------------------------------------------------------------------

CONCAT_ALLOWLIST = {
    ("idc_models_tpu/models/densenet.py", "dense_layer_concat"):
        "the block_impl=\"concat\" parity reference: the ONE place the "
        "literal concat semantics live, pinned bit-close against the "
        "packed path by tests/test_fused_conv.py",
}


def _scan_concat_calls(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    rel = str(path.relative_to(REPO)).replace("\\", "/")
    violations, live = [], set()

    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.Call):
                f = child.func
                name = (f.attr if isinstance(f, ast.Attribute)
                        else f.id if isinstance(f, ast.Name) else None)
                if name in ("concatenate", "concat"):
                    key = (rel, _enclosing_function(stack))
                    live.add(key)
                    if key not in CONCAT_ALLOWLIST:
                        violations.append((rel, child.lineno, name,
                                           key[1]))
            walk(child, stack + [child])

    walk(tree, [])
    return violations, live


def test_densenet_is_concat_free():
    violations, live = _scan_concat_calls(
        REPO / "idc_models_tpu" / "models" / "densenet.py")
    assert not violations, (
        "concatenate/concat calls in models/densenet.py outside the "
        "documented parity reference — dense blocks are concat-free by "
        "design (packed buffer + dynamic_update_slice; ISSUE 16); "
        "route new layers through the packed layout or extend the "
        f"documented CONCAT_ALLOWLIST: {violations}")
    stale = set(CONCAT_ALLOWLIST) - live
    assert not stale, (
        f"concat allowlist entries match no code: {stale}")


# -- ISSUE 11: no stray t_max-sized KV allocations in serve/ -------------
#
# The paged engine exists so HBM stops being reserved per slot's worst
# case; a new serve-side `zeros((..., t_max, ...))`-style KV allocation
# would quietly reintroduce the reservation the pool replaced. The scan
# flags allocation calls (zeros/ones/full/empty) whose shape is a
# literal tuple of rank >= 3 (KV-shaped — token-id buffers are 2-D) or
# `ring_decode.cache_shape(...)` / `index_cache_shape(...)`, the declared
# shapes of a contiguous cache, and mentions t_max anywhere inside it.

_ALLOC_CALLS = {"zeros", "ones", "full", "empty"}

# (path relative to the repo root, dotted enclosing-function path) ->
# why a t_max-sized KV allocation is correct there
TMAX_KV_ALLOWLIST = {
    ("idc_models_tpu/serve/engine.py", "_engine_fns.init_caches.mk"):
        "the CONTIGUOUS-mode constructor: per-slot [t_max] ring rows "
        "are exactly what that mode is — the paged twin "
        "(_paged_engine_fns) allocates the page pool instead",
    ("idc_models_tpu/serve/engine.py", "_engine_fns.init_caches.mk_index"):
        "the same constructor's index keys: a layer with an indexer "
        "caches one key a position beside K/V, and such a spec runs on "
        "the contiguous engine alone (paged KV refuses it by name)",
    ("idc_models_tpu/serve/engine.py", "_drafter_fns.init_caches.mk"):
        "the learned DRAFTER's ring: the draft LM is deliberately "
        "tiny (a few-MB student), so per-slot [t_max] rows cost "
        "kilobytes per slot and keep the batched propose ONE jitted "
        "program — paging the student would buy nothing and add a "
        "second page table to every slot lifecycle op",
}


def _enclosing_path(stack) -> str:
    names = [n.name for n in stack
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    return ".".join(names) if names else "<module>"


def _mentions_t_max(node) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id == "t_max":
            return True
        if isinstance(sub, ast.Attribute) and sub.attr == "t_max":
            return True
    return False


def _kv_shaped(node) -> bool:
    if isinstance(node, ast.Tuple):
        return len(node.elts) >= 3
    return (isinstance(node, ast.Call)
            and getattr(node.func, "id", getattr(node.func, "attr", None))
            in ("cache_shape", "index_cache_shape"))


def _scan_tmax_kv_allocs(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    rel = str(path.relative_to(REPO)).replace("\\", "/")
    violations, live = [], set()

    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            if (isinstance(child, ast.Call)
                    and isinstance(child.func, ast.Attribute)
                    and child.func.attr in _ALLOC_CALLS
                    and child.args
                    and _kv_shaped(child.args[0])
                    and _mentions_t_max(child.args[0])):
                key = (rel, _enclosing_path(stack))
                live.add(key)
                if key not in TMAX_KV_ALLOWLIST:
                    violations.append((rel, child.lineno, key[1]))
            walk(child, stack + [child])

    walk(tree, [])
    return violations, live


def test_no_tmax_sized_kv_allocations_in_serve():
    violations, live = [], set()
    for f in sorted((PACKAGE / "serve").rglob("*.py")):
        v, l = _scan_tmax_kv_allocs(f)
        violations.extend(v)
        live.update(l)
    assert not violations, (
        "t_max-sized KV allocation in serve/ outside the contiguous-"
        "mode constructor — per-slot worst-case reservations are what "
        "paged KV removed; allocate pool pages (or extend the "
        f"documented TMAX_KV_ALLOWLIST): {violations}")
    stale = set(TMAX_KV_ALLOWLIST) - live
    assert not stale, (
        f"t_max KV allowlist entries match no code: {stale}")


# -- ISSUE 13: no O(population)-sized allocations in the population
# federated layer ------------------------------------------------------
#
# federated/population.py exists so a 10k+ virtual-client population
# trains in memory bounded by the cohort/wave; ONE population-shaped
# numpy allocation (or a list comprehension over the population range)
# silently re-materializes what the lazy design removed. The scan
# flags allocation calls (zeros/ones/full/empty/arange) and list/set/
# dict comprehensions whose arguments mention the population count —
# the names `n_population`/`population_size`, or `.size` read off
# `self`/`population`/`pop`/`.population`.

_POP_ALLOC_CALLS = {"zeros", "ones", "full", "empty", "arange"}
_POP_COUNT_NAMES = {"n_population", "population_size"}
_POP_OWNER_NAMES = {"self", "population", "pop"}

# (path relative to the repo root, dotted enclosing-function path) ->
# why an O(population) allocation is correct there
POPULATION_ALLOC_ALLOWLIST = {
    # key = the shared _enclosing_path (function names only; the
    # method lives on ClientPopulation)
    ("idc_models_tpu/federated/population.py", "all_weights"):
        "the one deliberately O(population) helper: materializes the "
        "weight vector for validating the weighted sampler's "
        "distribution on SMALL test populations — documented as never "
        "on the training path",
}


def _mentions_population_count(node) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in _POP_COUNT_NAMES:
            return True
        if isinstance(sub, ast.Attribute) and sub.attr == "size":
            v = sub.value
            if isinstance(v, ast.Name) and v.id in _POP_OWNER_NAMES:
                return True
            if isinstance(v, ast.Attribute) and v.attr == "population":
                return True
    return False


def _scan_population_allocs(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    rel = str(path.relative_to(REPO)).replace("\\", "/")
    violations, live = [], set()

    def flag(node, stack, what):
        key = (rel, _enclosing_path(stack))
        live.add(key)
        if key not in POPULATION_ALLOC_ALLOWLIST:
            violations.append((rel, node.lineno, what, key[1]))

    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            if (isinstance(child, ast.Call)
                    and isinstance(child.func, ast.Attribute)
                    and child.func.attr in _POP_ALLOC_CALLS
                    and any(_mentions_population_count(a)
                            for a in list(child.args)
                            + [kw.value for kw in child.keywords])):
                flag(child, stack, child.func.attr)
            if (isinstance(child, (ast.ListComp, ast.SetComp,
                                   ast.DictComp))
                    and any(_mentions_population_count(g.iter)
                            for g in child.generators)):
                flag(child, stack, "comprehension")
            walk(child, stack + [child])

    walk(tree, [])
    return violations, live


def test_no_population_sized_allocations_in_population_layer():
    violations, live = [], set()
    for name in ("population.py", "async_fedavg.py"):
        v, l = _scan_population_allocs(
            PACKAGE / "federated" / name)
        violations.extend(v)
        live.update(l)
    assert not violations, (
        "population-count-shaped allocation in the population "
        "federated layer — virtual clients exist so memory is bounded "
        "by the cohort/wave, never the population (derive per-client "
        "state lazily from (seed, id), or extend the documented "
        f"POPULATION_ALLOC_ALLOWLIST): {violations}")
    stale = set(POPULATION_ALLOC_ALLOWLIST) - live
    assert not stale, (
        f"population-alloc allowlist entries match no code: {stale}")


def test_serve_handlers_quarantine_or_reraise():
    violations, live = [], set()
    for f in sorted((PACKAGE / "serve").rglob("*.py")):
        v, l = _scan_serve_handlers(f)
        violations.extend(v)
        live.update(l)
    assert not violations, (
        "serve/ except blocks that neither re-raise nor quarantine — a "
        "caught fault must recover the request or propagate to the "
        "engine-failure cleanup, never vanish (extend the documented "
        f"SERVE_EXCEPT_ALLOWLIST only for cleanup-path sites): "
        f"{violations}")
    stale = set(SERVE_EXCEPT_ALLOWLIST) - live
    assert not stale, (
        f"serve except allowlist entries match no code: {stale}")


# -- ISSUE 14: multi-tenant discipline ----------------------------------
#
# 1. Every TENANT-FACING metric registration must carry the `tenant`
#    label: an unlabeled "serve_tenant_*" series would aggregate every
#    tenant into one number — exactly the blindness the tenancy layer
#    exists to remove — and a dashboard built on it could never answer
#    "WHICH tenant is burning".
# 2. Cross-tenant state reads inside serve/tenancy.py are banned
#    outside a documented allowlist: the isolation story is only
#    auditable if every method provably touches ONE tenant's state,
#    with the few legitimately-global sites (registration, the stacked
#    adapter build, fleet rollups) enumerated and explained.

def _scan_tenant_metric_labels(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    rel = str(path.relative_to(PACKAGE)).replace("\\", "/")
    violations = []

    def has_tenant_label(call: ast.Call) -> bool:
        for kw in call.keywords:
            if kw.arg != "labels":
                continue
            if isinstance(kw.value, (ast.Tuple, ast.List)):
                return any(isinstance(e, ast.Constant)
                           and e.value == "tenant"
                           for e in kw.value.elts)
        return False

    def walk(node):
        for child in ast.iter_child_nodes(node):
            if (isinstance(child, ast.Call)
                    and isinstance(child.func, ast.Attribute)
                    and child.func.attr in _METRIC_FACTORIES
                    and child.args
                    and isinstance(child.args[0], ast.Constant)
                    and isinstance(child.args[0].value, str)
                    and "tenant" in child.args[0].value
                    and not has_tenant_label(child)):
                violations.append((rel, child.lineno,
                                   child.args[0].value))
            walk(child)

    walk(tree)
    return violations


def test_tenant_metric_registrations_carry_tenant_label():
    violations = []
    for f in sorted(PACKAGE.rglob("*.py")):
        if f.name == "metrics_registry.py":
            continue      # the factory definitions, not registrations
        violations.extend(_scan_tenant_metric_labels(f))
    assert not violations, (
        "tenant-facing metric registered WITHOUT the tenant label — an "
        "unlabeled serve_tenant_* series aggregates every tenant into "
        "one number, which can never answer 'which tenant is burning': "
        f"{violations}")


# function name in serve/tenancy.py -> why it legitimately sees every
# tenant (anything NOT here must read exactly one tenant's state)
TENANCY_CROSS_TENANT_ALLOWLIST = {
    "register": "duplicate-name check is the identity contract",
    "_check_adapter": "shape agreement is a property OF the set — one "
                      "[V, r] across every tenant's adapter",
    "build": "the one freeze point: stacks every adapter into the "
             "gather table, declares every SLO, builds every brownout",
    "names": "the documented fleet-rollup accessor (registration "
             "order = tid order)",
    "n_tenants": "set SIZE only — reads no tenant's state",
}

_TENANT_MAPS = {"_tenants", "brownouts"}


def _scan_cross_tenant_reads(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    violations, live = [], set()

    def names_tenant_map(node) -> bool:
        # self._tenants / self.brownouts, or a .values()/.items()/
        # .keys() view over them
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("values", "items", "keys")):
            node = node.func.value
        return (isinstance(node, ast.Attribute)
                and node.attr in _TENANT_MAPS)

    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            iter_sites = []
            if isinstance(child, (ast.For, ast.comprehension)):
                iter_sites.append(child.iter)
            if (isinstance(child, ast.Call)
                    and isinstance(child.func, ast.Name)
                    and child.func.id in ("list", "sorted", "len",
                                          "dict", "set", "tuple",
                                          "any", "all")):
                iter_sites.extend(child.args)
            for site in iter_sites:
                if not names_tenant_map(site):
                    continue
                fn = _enclosing_function(stack)
                live.add(fn)
                if fn not in TENANCY_CROSS_TENANT_ALLOWLIST:
                    violations.append(
                        (fn, getattr(child, "lineno",
                                     getattr(site, "lineno", 0))))
            walk(child, stack + [child])

    walk(tree, [])
    return violations, live


def test_no_cross_tenant_reads_in_tenancy():
    violations, live = _scan_cross_tenant_reads(
        PACKAGE / "serve" / "tenancy.py")
    assert not violations, (
        "cross-tenant state read in serve/tenancy.py outside the "
        "documented allowlist — tenancy methods must read ONE "
        "tenant's state so the isolation story stays auditable "
        "(extend TENANCY_CROSS_TENANT_ALLOWLIST only for genuinely "
        f"set-level operations, with the why): {violations}")
    stale = set(TENANCY_CROSS_TENANT_ALLOWLIST) - live
    assert not stale, (
        f"tenancy cross-tenant allowlist entries match no code: "
        f"{stale}")


# -- ISSUE 15: one sharding-resolution layer ----------------------------
#
# Placement policy lives in partition.py (regex->PartitionSpec rules)
# and the mesh/tp helpers; before this PR ten files constructed
# `NamedSharding(` / `PartitionSpec(` ad hoc, which is exactly how
# subsystems drift apart (the serve engine's trailing-None recompile
# was one symptom). The scan resolves `from jax.sharding import ...`
# aliases (including `PartitionSpec as P`) plus attribute-form
# `jax.sharding.X(` calls, and fails on any construction outside the
# documented allowlist. shard_map in/out specs are fold INTERNALS —
# per-device views of one program, not placement policy — so the
# explicit-collective files are allowlisted as such.

_SHARDING_CTORS = {"NamedSharding", "PartitionSpec"}

# relative path -> why constructing sharding objects there is correct
SHARDING_CTOR_ALLOWLIST = {
    "partition.py":
        "THE rule->spec resolution layer: adapts rule specs to leaf "
        "shapes/meshes and builds the resolved NamedShardings",
    "mesh.py":
        "the axis-aware construction helpers (sharding, replicated, "
        "batch_seq_spec/batch_seq_sharding) every other file calls",
    "tp.py":
        "the channel rule's readable shape-form (channel_spec) and "
        "its rules instance",
    "models/registry.py":
        "the per-model DEFAULT rule sets: rule definitions are "
        "(regex, PartitionSpec) pairs by construction",
    "ring_decode.py":
        "ring fold internals: shard_map per-device specs and the "
        "cache/pool layouts the folds are written against",
    "federated/fedavg.py":
        "explicit-collective shard_map in/out specs of the round "
        "program (client-axis fold internals)",
    "federated/population.py":
        "explicit-collective shard_map specs of the streamed wave "
        "program (client-axis fold internals)",
    "secure/fedavg.py":
        "explicit-collective shard_map specs of the secure-masking "
        "round (client-axis fold internals)",
}


def _scan_sharding_ctors(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    rel = str(path.relative_to(PACKAGE)).replace("\\", "/")
    aliases = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.ImportFrom)
                and node.module == "jax.sharding"):
            for a in node.names:
                if a.name in _SHARDING_CTORS:
                    aliases[a.asname or a.name] = a.name
    violations, live = [], set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        ctor = None
        if isinstance(fn, ast.Name) and fn.id in aliases:
            ctor = aliases[fn.id]
        elif (isinstance(fn, ast.Attribute)
              and fn.attr in _SHARDING_CTORS):
            ctor = fn.attr
        if ctor is None:
            continue
        live.add(rel)
        if rel not in SHARDING_CTOR_ALLOWLIST:
            violations.append((rel, node.lineno, ctor))
    return violations, live


def test_sharding_construction_single_layer():
    violations, live = [], set()
    for f in sorted(PACKAGE.rglob("*.py")):
        v, l = _scan_sharding_ctors(f)
        violations.extend(v)
        live.update(l)
    assert not violations, (
        "NamedSharding(/PartitionSpec( constructed outside the "
        "sharding layers — resolve placement through "
        "partition.PartitionRules (models/registry.py holds the "
        "per-model defaults) or the mesh.py helpers; extend the "
        "documented SHARDING_CTOR_ALLOWLIST only for fold-internal "
        f"shard_map specs: {violations}")
    stale = set(SHARDING_CTOR_ALLOWLIST) - live
    assert not stale, (
        f"sharding-constructor allowlist entries match no code: "
        f"{stale}")


# -- ISSUE 17: every checkpoint byte goes through an atomic commit ------
#
# checkpoint/sharded.py's completion contract (shard sha256s + a
# MANIFEST.json committed last) only holds if NO code path writes into
# a checkpoint directory around the tmp-then-`os.replace` commit
# helpers. A raw `open(..., "w")`, `np.save`, `Path.write_text`, or
# `shutil.copy*` under the checkpoint modules would be a torn-write
# hole the manifest cannot see. The scan walks the checkpoint-owning
# files and flags every write-capable call outside the documented
# atomic-commit allowlist.

_CKPT_FILES = (
    "idc_models_tpu/checkpoint/sharded.py",
    "idc_models_tpu/checkpoint/rollout.py",
    "idc_models_tpu/checkpoint/__init__.py",
    "idc_models_tpu/train/checkpoint.py",
)

# np.save/np.savez/np.savetxt and shutil's content-copying entry points
_RAW_WRITE_ATTRS = {"save", "savez", "savez_compressed", "savetxt",
                    "copy", "copy2", "copyfile", "copytree", "move",
                    "write_text", "write_bytes", "touch"}

# (repo-relative path, dotted enclosing-function path) -> why the raw
# write IS the atomic commit (or happens strictly before one)
CKPT_WRITE_ALLOWLIST = {
    ("idc_models_tpu/checkpoint/sharded.py", "_write_bytes"):
        "THE atomic byte commit: tmp-suffixed open('wb') + fsync + "
        "os.replace — every other writer (shards, fragments, manifest "
        "via _commit_json) funnels through here",
    ("idc_models_tpu/train/checkpoint.py", "save_checkpoint"):
        "digest write_text + marker touch land in <path>.tmp BEFORE "
        "the os.replace rename commit publishes the directory — a "
        "crash leaves a markerless partial checkpoint_exists refuses",
}


def _is_write_open(call: ast.Call) -> bool:
    if not (isinstance(call.func, ast.Name) and call.func.id == "open"):
        return False
    mode = None
    if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant):
        mode = call.args[1].value
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
            mode = kw.value.value
    if not isinstance(mode, str):
        # no literal mode = default "r"; a computed mode is opaque —
        # flag it so the writer documents an allowlist entry
        return len(call.args) >= 2 or any(k.arg == "mode"
                                          for k in call.keywords)
    return any(c in mode for c in "wax+")


def _scan_ckpt_writes(path: Path):
    tree = ast.parse(path.read_text(), filename=str(path))
    rel = str(path.relative_to(REPO)).replace("\\", "/")
    violations, live = [], set()

    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            hit = None
            if isinstance(child, ast.Call):
                if _is_write_open(child):
                    hit = "open(w)"
                elif (isinstance(child.func, ast.Attribute)
                      and child.func.attr in _RAW_WRITE_ATTRS):
                    hit = child.func.attr
            if hit is not None:
                key = (rel, _enclosing_path(stack))
                live.add(key)
                if key not in CKPT_WRITE_ALLOWLIST:
                    violations.append((rel, child.lineno, hit))
            walk(child, stack + [child])

    walk(tree, [])
    return violations, live


def test_checkpoint_writes_only_through_atomic_commit():
    violations, live = [], set()
    for rel in _CKPT_FILES:
        f = REPO / rel
        if not f.exists():
            continue
        v, l = _scan_ckpt_writes(f)
        violations.extend(v)
        live.update(l)
    assert not violations, (
        "raw write under the checkpoint modules outside the atomic-"
        "commit helpers — a byte that skips tmp-then-os.replace is a "
        "torn-write hole the manifest/marker contract cannot see; "
        "route it through checkpoint.sharded._write_bytes/_commit_json "
        "(or extend the documented CKPT_WRITE_ALLOWLIST): "
        f"{violations}")
    stale = set(CKPT_WRITE_ALLOWLIST) - live
    assert not stale, (
        f"checkpoint write allowlist entries match no code: {stale}")


# -- serve --drafter registry lockstep ----------------------------------
#
# cli.SERVE_DRAFTERS maps each `serve --drafter` choice to the class
# implementing it. Drift in either direction is a silent failure: a
# table entry naming a class without `propose` dies deep inside the
# scheduler on the first speculative cycle, and a drafter class added
# to models/ but left out of the table simply cannot be reached from
# the CLI. Classes implementing the contract for composition or
# testing only (deliberately NOT CLI-selectable) document themselves
# here — each entry says why.
DRAFTER_TABLE_EXEMPT = {
    # none today: every propose-bearing class under models/draft*.py
    # is CLI-reachable
}

_DRAFTER_FILES = ("models/draft.py", "models/draft_lm.py")


def _propose_bearing_classes():
    found = set()
    for rel in _DRAFTER_FILES:
        tree = ast.parse((PACKAGE / rel).read_text(), filename=rel)
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) and any(
                    isinstance(b, (ast.FunctionDef,
                                   ast.AsyncFunctionDef))
                    and b.name == "propose" for b in node.body):
                found.add(node.name)
    return found


def test_serve_drafter_table_entries_implement_the_contract():
    import importlib

    from idc_models_tpu.cli import SERVE_DRAFTERS

    for name, (module, cls_name, story) in SERVE_DRAFTERS.items():
        cls = getattr(importlib.import_module(module), cls_name)
        assert callable(getattr(cls, "propose", None)), (
            f"--drafter {name} maps to {module}.{cls_name}, which "
            f"has no propose(): every SERVE_DRAFTERS entry must "
            f"implement the models/draft.py contract")
        assert story, f"--drafter {name} carries no help story"


def test_every_drafter_class_is_cli_reachable_or_exempt():
    from idc_models_tpu.cli import SERVE_DRAFTERS

    listed = {cls for _mod, cls, _story in SERVE_DRAFTERS.values()}
    bearing = _propose_bearing_classes()
    orphans = bearing - listed - set(DRAFTER_TABLE_EXEMPT)
    assert not orphans, (
        "drafter class defines propose() but is reachable from "
        "neither `serve --drafter` (cli.SERVE_DRAFTERS) nor the "
        "documented DRAFTER_TABLE_EXEMPT — wire it into the table or "
        f"document why it is composition-only: {sorted(orphans)}")
    stale = set(DRAFTER_TABLE_EXEMPT) - bearing
    assert not stale, (
        f"drafter exemptions match no propose-bearing class: "
        f"{sorted(stale)}")


def test_drafter_argparse_choices_stay_in_lockstep():
    """The `--drafter` choices expression must be DERIVED from
    SERVE_DRAFTERS (not a hand-written list), so adding a table entry
    automatically surfaces it in argparse — and vice versa a choices
    edit without a table entry is impossible."""
    tree = ast.parse((PACKAGE / "cli.py").read_text(),
                     filename="cli.py")
    hit = None
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and node.args[0].value == "--drafter"):
            hit = node
    assert hit is not None, "serve grew no --drafter flag"
    choices = next((kw.value for kw in hit.keywords
                    if kw.arg == "choices"), None)
    assert choices is not None, "--drafter has no choices= keyword"
    names = {n.id for n in ast.walk(choices)
             if isinstance(n, ast.Name)}
    assert "SERVE_DRAFTERS" in names, (
        "--drafter choices are hand-written instead of derived from "
        "SERVE_DRAFTERS — the two will drift")
