"""tpu_lint — stdlib-ast linter for JAX/TPU anti-patterns in the engine.

The plan verifier (analysis/plan_lint.py) checks the plans the engine
builds; this linter checks the engine's own source for the patterns that
corrupt TPU performance or correctness silently:

* ``host-sync`` (kernel modules, ``ops/kernels/``): ``np.asarray``,
  ``jax.device_get``, ``.block_until_ready()``, ``.item()``, and
  ``int(...)``/``float(...)`` on non-constants — each one a device->host
  round trip; inside a traced kernel they serialize the pipeline.
* ``jit-branch`` (everywhere): ``if``/``while`` on a parameter of a
  ``@jax.jit`` function — data-dependent Python branching either fails to
  trace or silently burns one recompile per distinct value.
* ``jit-nested`` (everywhere): a ``jax.jit(...)`` call inside a function
  body — a fresh jitted callable per invocation, so the compile cache
  never hits (the engine's sanctioned pattern is
  ``utils.kernel_cache.cached_kernel``).
* ``plan-nondet`` (plan modules, ``plan/``): wall-clock/random/uuid calls
  in planning code — plan signatures and kernel-cache keys must be
  deterministic or caches silently miss (the ``Date.now`` class of bug).
* ``exec-no-metrics`` (exec modules, ``exec/``): a ``Tpu*Exec`` class that
  defines ``execute()`` but registers no metrics anywhere in its body
  (no ``ctx.metric(...)`` / ``ctx.registry.timer(...)`` call) — every
  exec's hot path must report at least its ESSENTIAL taxonomy metrics
  (metrics/registry.py, docs/monitoring.md) or the query profile shows a
  blind spot. Static approximation: the linter checks that SOME metric
  registration exists, not its level.
* ``except-too-broad`` (device-path modules: ``exec/``, ``memory/``,
  ``shuffle/``, ``io/``, plus the serving layer ``serve/`` with ZERO
  grandfathered sites — ISSUE 12): a bare ``except Exception`` (or
  untyped ``except:``) handler that never consults the retry taxonomy
  (memory/retry.py ``classify`` / ``RetryOOM`` / ``SplitAndRetryOOM``) —
  such handlers swallow device OOMs and transient faults the
  OOM-resilience layer exists to classify (docs/fault-tolerance.md).
  Static approximation: the handler is clean if its body references any
  taxonomy name.
* ``raw-thread`` (device-path modules plus ``data/`` and ``utils/``): a
  direct ``threading.Thread(...)`` construction — ad-hoc threads bypass
  the shared pipeline pool (exec/pipeline.py), escape the
  ``TpuSession.close`` leak check, and un-bound the pipeline's sized
  concurrency. Route through ``exec.pipeline.get_pool().submit`` or
  ``utils.prefetch.prefetch_iter`` instead; the pool's own spawn site
  carries the ignore marker.
* ``raw-lock`` (engine-wide): a direct ``threading.Lock()`` /
  ``RLock()`` / ``Condition()`` construction — raw locks are invisible
  to the concurrency layer (no name, no order tracking, no
  hold-across-blocking detection, absent from the docs/concurrency.md
  inventory). Route through ``utils/lockdep.py``'s ``lock()`` /
  ``rlock()`` / ``condition()`` factories, which return the raw
  primitive when ``TPU_LOCKDEP`` is off; lockdep.py's own construction
  sites are the baselined exception.
* ``blocking-no-span`` (device-path modules): a
  ``lockdep.blocking("kind")`` region not enclosed by (and not itself
  opening, in the same ``with`` statement) a trace span
  (``metrics/trace.py`` ``span(...)``) — every known-blocking wait in
  device-path code must be visible on the distributed-tracing timeline
  (ISSUE 13), or p99 analysis shows a gap exactly where the query
  stalled. Static approximation: some lexically-enclosing ``with`` in
  the same function (or the blocking call's own ``with``) must include
  a ``*.span(...)`` item.
* ``pallas-no-oracle`` (kernel modules, ``ops/kernels/``): a
  ``pallas_call`` site whose enclosing function's docstring does not
  name its jnp oracle twin (the word "oracle"). The engine has no Pallas
  kernel today (the jnp kernels are the only path); one that arrives
  for a measured bottleneck keeps the jnp implementation it replaces as
  its bit-identity oracle, and the docstring reference is the
  statically-checkable trace of that.

Existing debt is RATCHETED, not flooded: the checked-in baseline
(``tools/tpu_lint_baseline.json``) records per-(file, rule) counts; the
lint fails only when a count exceeds its baseline. Lowering counts below
baseline prints a reminder to tighten with ``--update-baseline``.

Suppress a finding by putting ``# tpu-lint: ignore`` on the offending
line (counts as a whitelisted sync point for ``host-sync``).

The static concurrency pass (``analysis/concurrency.py`` — lock-order
cycles, hold-across-blocking, unguarded shared writes) runs under the
same ratchet discipline against ``tools/lock_order_baseline.json`` via
``--concurrency``; see docs/concurrency.md.

CLI::

    python -m tools.tpu_lint            # check against the baseline
    python -m tools.tpu_lint --list     # print every finding
    python -m tools.tpu_lint --update-baseline
    python -m tools.tpu_lint --concurrency [--list | --update-baseline]
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

#: relpath prefixes that scope the path-restricted rules
KERNEL_SCOPE = ("ops/kernels/",)
PLAN_SCOPE = ("plan/",)
EXEC_SCOPE = ("exec/",)
#: ml/ joins the device-path scopes with ZERO grandfathered sites
#: (ISSUE 14): the ML subsystem's registry/export/score paths do device
#: work and must honor the same except-too-broad / blocking-no-span /
#: raw-thread discipline as every other device layer (raw-lock is
#: engine-wide already).
DEVICE_SCOPE = ("exec/", "memory/", "shuffle/", "io/", "ml/")
#: except-too-broad also covers the serving layer (ISSUE 12, ZERO
#: grandfathered sites): a handler there that swallows classified faults
#: breaks the typed-error contract every client depends on.
BROAD_EXCEPT_SCOPE = DEVICE_SCOPE + ("serve/",)
#: raw-thread also covers the batch/upload and shared-utility layers —
#: everywhere a stray Thread could carry device work past the pool.
RAW_THREAD_SCOPE = DEVICE_SCOPE + ("data/", "utils/")

#: retry-taxonomy names whose presence marks a broad handler as
#: classified (except-too-broad)
_TAXONOMY_NAMES = frozenset({"classify", "Classification", "RetryOOM",
                             "SplitAndRetryOOM"})

#: attribute-call names that count as "registers a metric" for
#: exec-no-metrics (ctx.metric, ctx.registry.timer/add, registry sinks)
_METRIC_CALL_ATTRS = frozenset({"metric", "timer"})
#: module-level metric helpers (exec/execs.py) that also count
_METRIC_HELPER_NAMES = frozenset({"_tick", "_counted_stream"})

IGNORE_MARKER = "tpu-lint: ignore"

_NONDET_MODULE_CALLS = {
    "time": {"time", "time_ns", "monotonic", "perf_counter"},
    "random": None,   # any attribute
    "uuid": {"uuid1", "uuid3", "uuid4", "uuid5"},
    "os": {"urandom"},
    "secrets": None,
}


@dataclasses.dataclass(frozen=True)
class Violation:
    path: str    # relpath under the scan root, '/' separators
    rule: str
    lineno: int
    message: str

    @property
    def key(self) -> str:
        return f"{self.path}::{self.rule}"

    def __str__(self) -> str:
        return f"{self.path}:{self.lineno}: [{self.rule}] {self.message}"


def _is_jit_decorator(d: ast.expr) -> bool:
    """jax.jit / jit / partial(jax.jit, ...) / jax.jit(...) decorators."""
    if isinstance(d, ast.Attribute) and d.attr == "jit":
        return True
    if isinstance(d, ast.Name) and d.id == "jit":
        return True
    if isinstance(d, ast.Call):
        if _is_jit_decorator(d.func):
            return True
        return any(_is_jit_decorator(a) for a in d.args)
    return False


def _call_root(func: ast.expr) -> Optional[str]:
    """Leftmost Name of a dotted call target (``jax`` in jax.x.y(...))."""
    while isinstance(func, ast.Attribute):
        func = func.value
    return func.id if isinstance(func, ast.Name) else None


class _FileLinter(ast.NodeVisitor):
    def __init__(self, relpath: str, lines: List[str]):
        self.relpath = relpath
        self.lines = lines
        self.in_kernel = relpath.startswith(KERNEL_SCOPE)
        self.in_plan = relpath.startswith(PLAN_SCOPE)
        self.in_exec = relpath.startswith(EXEC_SCOPE)
        self.in_device = relpath.startswith(DEVICE_SCOPE)
        self.in_broad_except = relpath.startswith(BROAD_EXCEPT_SCOPE)
        self.in_raw_thread = relpath.startswith(RAW_THREAD_SCOPE)
        self.violations: List[Violation] = []
        #: stack of (is_jit, frozenset(param names)) for enclosing functions
        self._funcs: List[Tuple[bool, frozenset]] = []
        #: stack of enclosing-function docstrings (pallas-no-oracle)
        self._func_docs: List[str] = []
        #: stack of (function depth, with-statement-has-span-item) for
        #: enclosing ``with`` statements (blocking-no-span)
        self._withs: List[Tuple[int, bool]] = []

    # -- helpers ------------------------------------------------------------
    def _suppressed(self, node: ast.AST) -> bool:
        line = self.lines[node.lineno - 1] if node.lineno <= len(self.lines) \
            else ""
        return IGNORE_MARKER in line

    def _flag(self, node: ast.AST, rule: str, message: str):
        if not self._suppressed(node):
            self.violations.append(
                Violation(self.relpath, rule, node.lineno, message))

    def _jit_params(self) -> Optional[frozenset]:
        for is_jit, params in reversed(self._funcs):
            if is_jit:
                return params
        return None

    # -- function tracking ---------------------------------------------------
    def _visit_func(self, node):
        is_jit = any(_is_jit_decorator(d) for d in node.decorator_list)
        args = node.args
        params = frozenset(
            a.arg for a in [*args.posonlyargs, *args.args, *args.kwonlyargs]
            + ([args.vararg] if args.vararg else [])
            + ([args.kwarg] if args.kwarg else []))
        self._funcs.append((is_jit, params))
        self._func_docs.append(ast.get_docstring(node) or "")
        self.generic_visit(node)
        self._funcs.pop()
        self._func_docs.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    @staticmethod
    def _is_span_call(expr: ast.expr) -> bool:
        """A ``with`` item that opens a trace span: ``*.span(...)`` or a
        bare ``span(...)`` (metrics/trace.py's call-site helper)."""
        if not isinstance(expr, ast.Call):
            return False
        f = expr.func
        return (isinstance(f, ast.Attribute) and f.attr == "span") \
            or (isinstance(f, ast.Name) and f.id == "span")

    def visit_With(self, node: ast.With):
        has_span = any(self._is_span_call(item.context_expr)
                       for item in node.items)
        self._withs.append((len(self._funcs), has_span))
        self.generic_visit(node)
        self._withs.pop()

    visit_AsyncWith = visit_With

    def visit_ClassDef(self, node: ast.ClassDef):
        if self.in_exec:
            self._check_exec_metrics(node)
        self.generic_visit(node)

    def _check_exec_metrics(self, node: ast.ClassDef):
        """exec-no-metrics: a Tpu*Exec defining execute() must register at
        least one metric somewhere in the class (subclasses inheriting
        execute() are covered by their base)."""
        import re
        if not re.fullmatch(r"Tpu\w+Exec", node.name):
            return
        has_execute = any(
            isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n.name == "execute" for n in node.body)
        if not has_execute:
            return
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            if isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in _METRIC_CALL_ATTRS:
                return
            if isinstance(sub.func, ast.Name) \
                    and sub.func.id in _METRIC_HELPER_NAMES:
                return
        self._flag(node, "exec-no-metrics",
                   f"{node.name} defines execute() but never registers a "
                   "metric (ctx.metric / ctx.registry.timer); its hot path "
                   "is invisible to the query profile — wire up the "
                   "ESSENTIAL taxonomy (docs/monitoring.md)")

    # -- rules ---------------------------------------------------------------
    def visit_Call(self, node: ast.Call):
        func = node.func
        root = _call_root(func)
        if self.in_kernel:
            self._check_host_sync(node, func, root)
            self._check_pallas_oracle(node, func)
        if self.in_plan:
            self._check_nondet(node, func, root)
        if self.in_raw_thread:
            self._check_raw_thread(node, func, root)
        if self.in_device:
            self._check_blocking_span(node, func, root)
        self._check_raw_lock(node, func, root)
        if self._funcs and (
                (root == "jax" and isinstance(func, ast.Attribute)
                 and func.attr == "jit")
                or (isinstance(func, ast.Name) and func.id == "jit")):
            self._flag(node, "jit-nested",
                       "jax.jit called inside a function body compiles a "
                       "fresh program per call; route through "
                       "utils.kernel_cache.cached_kernel")
        self.generic_visit(node)

    def _check_host_sync(self, node: ast.Call, func, root):
        if isinstance(func, ast.Attribute):
            if func.attr == "asarray" and root in ("np", "numpy"):
                self._flag(node, "host-sync",
                           "np.asarray on a device value forces a "
                           "device->host transfer inside a kernel module")
            elif func.attr == "device_get":
                self._flag(node, "host-sync",
                           "jax.device_get is a blocking device->host sync")
            elif func.attr == "block_until_ready":
                self._flag(node, "host-sync",
                           ".block_until_ready() stalls the dispatch "
                           "pipeline")
            elif func.attr == "item" and not node.args:
                self._flag(node, "host-sync",
                           ".item() on a traced/device value is a hidden "
                           "device->host sync")
        elif isinstance(func, ast.Name) and func.id in ("int", "float") \
                and len(node.args) == 1 \
                and not isinstance(node.args[0], ast.Constant):
            self._flag(node, "host-sync",
                       f"{func.id}(...) on a non-constant concretizes a "
                       "traced value (host sync inside a kernel module)")

    def _check_pallas_oracle(self, node: ast.Call, func):
        """pallas-no-oracle: every ``pallas_call`` site must sit inside a
        function whose docstring names its jnp oracle twin — the
        statically-checkable trace of the oracle discipline (a Pallas
        kernel is tested bit for bit against the jnp kernel it
        replaces)."""
        name = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else None)
        if name != "pallas_call":
            return
        if self._func_docs and "oracle" in self._func_docs[-1].lower():
            return
        self._flag(node, "pallas-no-oracle",
                   "pallas_call site whose enclosing function's docstring "
                   "does not name its jnp oracle twin; a Pallas kernel "
                   "is tested bit for bit against the jnp kernel it "
                   "replaces — say which one (e.g. 'Oracle: "
                   "jax.ops.segment_sum') in the docstring")

    def _check_raw_thread(self, node: ast.Call, func, root):
        """raw-thread: device-path (+ data/utils) modules must not spawn
        ad-hoc threads — they bypass the shared pipeline pool's sizing
        and the TpuSession.close leak check (exec/pipeline.py)."""
        is_thread = (isinstance(func, ast.Attribute)
                     and func.attr == "Thread" and root == "threading") \
            or (isinstance(func, ast.Name) and func.id == "Thread")
        if is_thread:
            self._flag(node, "raw-thread",
                       "threading.Thread in a device-path module bypasses "
                       "the shared pipeline pool (worker reuse, sized "
                       "concurrency, session-close leak check); route "
                       "through exec.pipeline.get_pool().submit or "
                       "utils.prefetch.prefetch_iter")

    def _check_raw_lock(self, node: ast.Call, func, root):
        """raw-lock (engine-wide): threading.Lock/RLock/Condition must
        route through the utils/lockdep.py factories so every engine lock
        is named, order-tracked, and listed in the docs/concurrency.md
        inventory; lockdep.py's own sites are baselined."""
        names = ("Lock", "RLock", "Condition")
        is_raw = (isinstance(func, ast.Attribute) and func.attr in names
                  and root == "threading") \
            or (isinstance(func, ast.Name) and func.id in names)
        if is_raw:
            kind = func.attr if isinstance(func, ast.Attribute) \
                else func.id
            factory = {"Lock": "lock", "RLock": "rlock",
                       "Condition": "condition"}[kind]
            self._flag(node, "raw-lock",
                       f"threading.{kind}() constructed outside "
                       "utils/lockdep.py is invisible to the concurrency "
                       "layer (no lock-order tracking, no "
                       "hold-across-blocking detection, missing from the "
                       "docs/concurrency.md inventory); use "
                       f"lockdep.{factory}(\"<module>.<name>\")")

    def _check_blocking_span(self, node: ast.Call, func, root):
        """blocking-no-span: a ``lockdep.blocking(...)`` marker in a
        device-path module must sit inside (or share its ``with``
        statement with) a trace span — blocking waits are exactly the
        regions a p99 timeline must show, so an unspanned one is a
        guaranteed attribution gap (metrics/trace.py, ISSUE 13)."""
        if not (isinstance(func, ast.Attribute) and func.attr == "blocking"
                and root is not None and root.lstrip("_") == "lockdep"):
            return
        depth = len(self._funcs)
        for d, has_span in self._withs:
            if d == depth and has_span:
                return
        self._flag(node, "blocking-no-span",
                   "lockdep.blocking region is not enclosed by (or "
                   "sharing a `with` statement with) a trace span; every "
                   "known-blocking wait in device-path code must be "
                   "visible on the tracing timeline — open a "
                   "metrics/trace span around it (ISSUE 13, "
                   "docs/monitoring.md#distributed-tracing)")

    def _check_nondet(self, node: ast.Call, func, root):
        if not isinstance(func, ast.Attribute):
            return
        allowed = _NONDET_MODULE_CALLS.get(root or "")
        if root in _NONDET_MODULE_CALLS \
                and (allowed is None or func.attr in allowed):
            self._flag(node, "plan-nondet",
                       f"{root}.{func.attr}() is nondeterministic; plan "
                       "construction must be reproducible (plan signatures "
                       "and kernel-cache keys depend on it)")
        elif func.attr in ("now", "utcnow", "today") \
                and isinstance(func.value, (ast.Name, ast.Attribute)):
            tail = func.value.attr if isinstance(func.value, ast.Attribute) \
                else func.value.id
            if tail in ("datetime", "date"):
                self._flag(node, "plan-nondet",
                           f"{tail}.{func.attr}() reads the wall clock in "
                           "plan code")

    def visit_ExceptHandler(self, node: ast.ExceptHandler):
        if self.in_broad_except:
            self._check_broad_except(node)
        self.generic_visit(node)

    def _check_broad_except(self, node: ast.ExceptHandler):
        """except-too-broad: a catch-everything handler in a device-path
        module must route through the retry taxonomy (any reference to
        classify/Classification/RetryOOM/SplitAndRetryOOM in the handler
        counts), or it silently swallows OOM/transient faults the
        OOM-resilience layer should see."""
        t = node.type
        broad = t is None or (isinstance(t, ast.Name)
                              and t.id in ("Exception", "BaseException"))
        if not broad:
            return
        for sub in ast.walk(node):
            names = []
            if isinstance(sub, ast.Name):
                names.append(sub.id)
            elif isinstance(sub, ast.Attribute):
                names.append(sub.attr)
            for n in names:
                # exact taxonomy names, or classify-routing helpers
                # (classify / _classify_probe_failure / ...)
                if n in _TAXONOMY_NAMES or "classify" in n.lower():
                    return
        self._flag(node, "except-too-broad",
                   "bare `except Exception` in a device-path module "
                   "swallows the OOMs and transient faults the retry "
                   "taxonomy classifies; route through "
                   "memory/retry.classify or narrow the exception type")

    def _check_branch(self, node):
        params = self._jit_params()
        if params is not None:
            names = {n.id for n in ast.walk(node.test)
                     if isinstance(n, ast.Name)}
            hit = sorted(names & params)
            if hit:
                kind = "if" if isinstance(node, ast.If) else "while"
                self._flag(node, "jit-branch",
                           f"Python `{kind}` on traced parameter(s) "
                           f"{', '.join(hit)} inside a @jax.jit function; "
                           "use lax.cond/lax.while_loop or hoist to a "
                           "static argument")
        self.generic_visit(node)

    visit_If = _check_branch
    visit_While = _check_branch


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def lint_tree(root: str) -> List[Violation]:
    """Lint every .py file under ``root`` (the package directory)."""
    out: List[Violation] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d not in ("__pycache__", "_build"))
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            full = os.path.join(dirpath, fn)
            rel = os.path.relpath(full, root).replace(os.sep, "/")
            with open(full, encoding="utf-8") as f:
                src = f.read()
            try:
                tree = ast.parse(src, filename=full)
            except SyntaxError as e:
                out.append(Violation(rel, "parse-error", e.lineno or 0,
                                     str(e)))
                continue
            linter = _FileLinter(rel, src.splitlines())
            linter.visit(tree)
            out.extend(linter.violations)
    return out


def counts_of(violations: List[Violation]) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for v in violations:
        counts[v.key] = counts.get(v.key, 0) + 1
    return counts


def compare_to_baseline(violations: List[Violation],
                        baseline: Dict[str, int]
                        ) -> Tuple[List[Violation], List[str]]:
    """(new violations above the ratchet, keys now below baseline)."""
    counts = counts_of(violations)
    new: List[Violation] = []
    by_key: Dict[str, List[Violation]] = {}
    for v in violations:
        by_key.setdefault(v.key, []).append(v)
    for key, vs in sorted(by_key.items()):
        allowed = baseline.get(key, 0)
        if len(vs) > allowed:
            # Report the trailing occurrences as the new ones (stable for
            # appends; any fix inside the file re-anchors the ratchet).
            new.extend(vs[allowed:])
    improved = sorted(k for k, n in baseline.items()
                      if counts.get(k, 0) < n)
    return new, improved


def load_baseline(path: str) -> Dict[str, int]:
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    return dict(data.get("counts", {}))


def write_baseline(path: str, violations: List[Violation]):
    data = {
        "comment": "Ratcheted tpu_lint debt: per (file, rule) finding "
                   "counts. Regenerate with "
                   "`python -m tools.tpu_lint --update-baseline`; counts "
                   "may only go DOWN in review.",
        "counts": dict(sorted(counts_of(violations).items())),
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=False)
        f.write("\n")


def load_concurrency():
    """Load THIS repo's analysis/concurrency.py by FILE PATH (it is
    standalone by design): importing it as a package submodule would pull
    in spark_rapids_tpu/__init__ and therefore jax, which the lint CLI
    must not need. Always resolved relative to tpu_lint itself — the
    --root flag selects the tree to ANALYZE, never where the analyzer
    lives."""
    import importlib.util
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo_root, "spark_rapids_tpu", "analysis",
                        "concurrency.py")
    spec = importlib.util.spec_from_file_location("_tpu_concurrency", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_tpu_concurrency"] = mod
    spec.loader.exec_module(mod)
    return mod


def main(argv: Optional[List[str]] = None) -> int:
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(
        prog="tools.tpu_lint",
        description="AST linter for JAX/TPU anti-patterns (ratcheted)")
    ap.add_argument("--root",
                    default=os.path.join(repo_root, "spark_rapids_tpu"),
                    help="package directory to lint")
    ap.add_argument("--baseline",
                    default=os.path.join(repo_root, "tools",
                                         "tpu_lint_baseline.json"))
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from the current findings")
    ap.add_argument("--list", action="store_true",
                    help="print every finding, baselined or not")
    ap.add_argument("--concurrency", action="store_true",
                    help="run the static concurrency pass "
                         "(analysis/concurrency.py) against its own "
                         "ratchet, tools/lock_order_baseline.json")
    ap.add_argument("--concurrency-baseline",
                    default=os.path.join(repo_root, "tools",
                                         "lock_order_baseline.json"))
    args = ap.parse_args(argv)

    if args.concurrency:
        conc = load_concurrency()
        return conc.run(args.root, args.concurrency_baseline,
                        update=args.update_baseline, list_all=args.list)

    violations = lint_tree(args.root)
    if args.update_baseline:
        write_baseline(args.baseline, violations)
        print(f"baseline updated: {len(violations)} finding(s) across "
              f"{len(counts_of(violations))} (file, rule) key(s)")
        return 0
    if args.list:
        for v in violations:
            print(v)
    baseline = load_baseline(args.baseline)
    new, improved = compare_to_baseline(violations, baseline)
    for k in improved:
        print(f"note: {k} is below its baseline count — tighten the "
              "ratchet with --update-baseline")
    if new:
        print(f"{len(new)} NEW violation(s) above the baseline:",
              file=sys.stderr)
        for v in new:
            print(f"  {v}", file=sys.stderr)
        return 1
    print(f"tpu_lint clean: {len(violations)} baselined finding(s), "
          "0 new")
    return 0


if __name__ == "__main__":
    sys.exit(main())
