"""The tpu-lint rule pack.

Each rule targets a bug class that has no runtime guard in this repo
(docs/STATIC_ANALYSIS.md describes each with examples):

- jax-host-sync:      host synchronization inside jit'd functions.
- lock-discipline:    blocking calls under a held lock; attributes
                      mutated both inside and outside lock scopes.
- env-discipline:     os.environ reads outside settings.py / config/.
- dtype-discipline:   implicit dtype promotion in kernel scatter calls.
- timing-discipline:  wall clock (time.time / datetime.now/utcnow)
                      in duration arithmetic.
- metrics-discipline: interpolated (unbounded-cardinality) metric
                      names in stats registrations.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .engine import FileContext, Rule

# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------


def dotted_name(node: ast.AST) -> Optional[str]:
    """'jax.jit' for Attribute/Name chains, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted_name(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def terminal_name(node: ast.AST) -> Optional[str]:
    """The last identifier of a Name/Attribute chain ('_completion_q'
    for `self._completion_q`)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


_JIT_CALLEES = {
    "jax.jit",
    "jit",
    "jax.pmap",
    "pmap",
    "jax.shard_map",
    "shard_map",
}

_PARTIAL_CALLEES = {"functools.partial", "partial"}


def _jit_transform_of(deco: ast.AST) -> Optional[ast.Call]:
    """If `deco` is a jit/pmap/shard_map decorator (bare, called, or
    functools.partial-wrapped), return the Call carrying its kwargs
    (static_argnums etc.), or a synthetic None for bare decorators."""
    if isinstance(deco, (ast.Name, ast.Attribute)):
        return ast.Call(func=deco, args=[], keywords=[]) if (
            dotted_name(deco) in _JIT_CALLEES
        ) else None
    if isinstance(deco, ast.Call):
        callee = dotted_name(deco.func)
        if callee in _JIT_CALLEES:
            return deco
        if callee in _PARTIAL_CALLEES and deco.args:
            if dotted_name(deco.args[0]) in _JIT_CALLEES:
                return deco
    return None


def _literal_ints(node: ast.AST) -> List[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out: List[int] = []
        for e in node.elts:
            out.extend(_literal_ints(e))
        return out
    return []


def _literal_strs(node: ast.AST) -> List[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out: List[str] = []
        for e in node.elts:
            out.extend(_literal_strs(e))
        return out
    return []


def _static_params(
    fn: ast.FunctionDef, transform: ast.Call
) -> Set[str]:
    """Parameter NAMES the jit decorator marks static (traceable as
    Python values: control flow on them is fine)."""
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    static: Set[str] = set()
    for kw in transform.keywords:
        if kw.arg == "static_argnums":
            for i in _literal_ints(kw.value):
                if 0 <= i < len(params):
                    static.add(params[i])
        elif kw.arg == "static_argnames":
            static.update(_literal_strs(kw.value))
    return static


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


# ---------------------------------------------------------------------------
# jax-host-sync
# ---------------------------------------------------------------------------


class JaxHostSyncRule(Rule):
    """Host synchronization inside jit'd code.

    A `.item()`, `float()`, `np.asarray`, `jax.device_get`, or Python
    branch on a tracer inside a `jax.jit`/`pmap`/`shard_map` function
    forces a device->host readback per call — it turns the vectorized
    INCR+EXPIRE kernel into a per-batch RTT and silently destroys
    serving throughput (the reason the compact-readback work exists at
    all, benchmarks/PERF_NOTES.md).

    Jitted functions are found three ways:
    1. decorated with jit/pmap/shard_map (bare or functools.partial);
    2. passed by name (or ``self.name``) into a jit/pmap/shard_map
       call anywhere in the module (``jax.jit(jax.shard_map(body))``);
    3. passed into a local *jit-wrapper*: a function that forwards one
       of its own parameters into a jit call (the ``_build`` pattern
       in parallel/sharded.py).

    The tracer-control-flow check only runs on DECORATED functions,
    where static_argnums/static_argnames are visible; by-reference
    jitted functions often bind static config through default
    arguments, which the AST cannot distinguish from traced inputs.
    """

    id = "jax-host-sync"
    description = "host synchronization inside a jit'd function"
    interests = ()  # needs Call/If/While/For inside precomputed scopes

    _SYNC_CALLEES = {
        "jax.device_get": "jax.device_get() copies device->host",
        "np.asarray": "np.asarray() on a tracer forces a host copy",
        "numpy.asarray": "numpy.asarray() on a tracer forces a host copy",
        "np.array": "np.array() on a tracer forces a host copy",
        "numpy.array": "numpy.array() on a tracer forces a host copy",
    }
    _SYNC_METHODS = {
        "item": ".item() blocks on the device and copies to host",
        "tolist": ".tolist() blocks on the device and copies to host",
        "block_until_ready": ".block_until_ready() stalls the pipeline",
    }
    _CAST_BUILTINS = {"float", "int", "bool"}

    def begin_file(self, ctx: FileContext) -> None:
        # fn node -> static param names (None key content for
        # by-reference jitted functions: no static info).
        self._jitted: Dict[ast.AST, Optional[Set[str]]] = {}
        self._collect_jitted(ctx.tree)

    # -- jitted-function discovery --------------------------------------

    def _collect_jitted(self, tree: ast.Module) -> None:
        fn_defs: Dict[str, List[ast.FunctionDef]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn_defs.setdefault(node.name, []).append(node)

        # 1. decorator-jitted (static info available)
        for defs in fn_defs.values():
            for fn in defs:
                for deco in fn.decorator_list:
                    transform = _jit_transform_of(deco)
                    if transform is not None:
                        self._jitted[fn] = _static_params(fn, transform)

        # 2. by-reference: names passed into jit/shard_map/pmap calls
        referenced: Set[str] = set()
        wrapper_names: Set[str] = set()
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if dotted_name(node.func) in _JIT_CALLEES:
                for arg in node.args:
                    name = terminal_name(arg)
                    if name:
                        referenced.add(name)

        # 3. jit-wrappers: a function that forwards one of its OWN
        #    parameters into a jit call (sharded.py `_build`).
        for defs in fn_defs.values():
            for fn in defs:
                params = {a.arg for a in fn.args.args + fn.args.posonlyargs}
                for node in ast.walk(fn):
                    if (
                        isinstance(node, ast.Call)
                        and dotted_name(node.func) in _JIT_CALLEES
                    ):
                        for arg in node.args:
                            if (
                                isinstance(arg, ast.Name)
                                and arg.id in params
                            ):
                                wrapper_names.add(fn.name)
        if wrapper_names:
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                if terminal_name(node.func) in wrapper_names:
                    for arg in node.args:
                        name = terminal_name(arg)
                        if name:
                            referenced.add(name)

        for name in referenced:
            for fn in fn_defs.get(name, ()):
                self._jitted.setdefault(fn, None)

    def _enclosing_jitted(
        self, parents: Sequence[ast.AST]
    ) -> Optional[ast.AST]:
        for p in reversed(parents):
            if p in self._jitted:
                return p
        return None

    # -- dispatch --------------------------------------------------------

    def visit(self, node, parents, ctx: FileContext) -> None:
        if not self._jitted:
            return
        fn = self._enclosing_jitted(parents)
        if fn is None:
            return
        if isinstance(node, ast.Call):
            self._check_call(node, ctx)
        elif isinstance(node, (ast.If, ast.While)):
            self._check_branch(node, node.test, fn, ctx)
        elif isinstance(node, ast.For):
            self._check_branch(node, node.iter, fn, ctx)

    def _check_call(self, node: ast.Call, ctx: FileContext) -> None:
        callee = dotted_name(node.func)
        if callee in self._SYNC_CALLEES:
            self.report(
                ctx, node, f"{self._SYNC_CALLEES[callee]} inside jit"
            )
            return
        if callee in self._CAST_BUILTINS and node.args:
            arg = node.args[0]
            if not isinstance(arg, ast.Constant):
                self.report(
                    ctx,
                    node,
                    f"{callee}() on a traced value concretizes it on "
                    "host inside jit (use jnp casts / lax ops)",
                )
            return
        if isinstance(node.func, ast.Attribute):
            meth = node.func.attr
            if meth in self._SYNC_METHODS:
                self.report(
                    ctx, node, f"{self._SYNC_METHODS[meth]} inside jit"
                )

    def _check_branch(
        self, node: ast.AST, test: ast.AST, fn: ast.AST, ctx: FileContext
    ) -> None:
        static = self._jitted.get(fn)
        if static is None:
            return  # by-reference jitted: static args unknowable
        params = {
            a.arg for a in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
        }
        traced = (params - static - {"self"}) & _names_in(test)
        if traced:
            kind = type(node).__name__.lower()
            self.report(
                ctx,
                node,
                f"python `{kind}` on traced argument(s) "
                f"{sorted(traced)} inside jit (data-dependent control "
                "flow needs lax.cond/select/fori_loop)",
            )


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------

# Terminal-name fragments that identify a synchronization primitive in
# a `with X:` context expression.
_LOCKISH_FRAGMENTS = ("lock", "mutex", "_cv", "cond")


def _lockish(node: ast.AST) -> Optional[str]:
    """Lock identity string if `node` looks like a lock object."""
    name = terminal_name(node)
    if name is None:
        return None
    low = name.lower()
    if any(f in low for f in _LOCKISH_FRAGMENTS) or low == "cv":
        return dotted_name(node) or name
    return None


class LockDisciplineRule(Rule):
    """Race/deadlock discipline in the threaded backends.

    Two checks (the poor man's `go vet` + race detector for
    write_behind/dispatcher/cluster code):

    1. BLOCKING CALLS UNDER A LOCK: `time.sleep`, socket/grpc I/O,
       `queue.get()` with no timeout, and untimed `.wait()` on a
       DIFFERENT object than the held lock, inside a `with <lock>:`
       block.  Every RPC thread contending on that lock stalls behind
       the sleeper (the whole reason the dispatcher's intake is a
       one-swap list, dispatcher.py).

    2. SPLIT-LOCK ATTRIBUTE MUTATION: a `self.X` assigned both inside
       and outside `with <lock>:` scopes in the same class (outside
       ``__init__``, whose writes happen-before thread start) is a
       data-race smell: either the lock is unnecessary or the unlocked
       write races it.

    Lock scopes are recognized by terminal name: `with self._view_lock:`,
    `with cv:`, names containing lock/mutex/cond/_cv.
    """

    id = "lock-discipline"
    description = "blocking call or unlocked mutation under lock discipline"
    interests = ()

    _BLOCKING_METHODS = {
        "recv",
        "recvfrom",
        "sendall",
        "connect",
        "accept",
    }
    _QUEUEISH = ("queue", "_q")

    def begin_file(self, ctx: FileContext) -> None:
        # (class name, attr) -> {"locked": node|None, "unlocked": node|None}
        self._attr_writes: Dict[Tuple[str, str], Dict[str, ast.AST]] = {}

    # -- helpers ---------------------------------------------------------

    def _held_locks(self, parents: Sequence[ast.AST]) -> List[str]:
        held: List[str] = []
        for p in parents:
            if isinstance(p, ast.With):
                for item in p.items:
                    lock = _lockish(item.context_expr)
                    if lock is not None:
                        held.append(lock)
        return held

    @staticmethod
    def _has_timeout(node: ast.Call) -> bool:
        if any(kw.arg in ("timeout", "timeout_s") for kw in node.keywords):
            return True
        # queue.get(block, timeout) / lock.acquire(blocking, timeout):
        # a second positional arg is the timeout.
        return len(node.args) >= 2

    def _enclosing(
        self, parents: Sequence[ast.AST]
    ) -> Tuple[Optional[str], Optional[str]]:
        """(enclosing class name, enclosing function name)."""
        cls = fn = None
        for p in parents:
            if isinstance(p, ast.ClassDef):
                cls, fn = p.name, None
            elif isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn = p.name
        return cls, fn

    # -- dispatch --------------------------------------------------------

    def visit(self, node, parents, ctx: FileContext) -> None:
        if isinstance(node, ast.Call):
            self._check_blocking(node, parents, ctx)
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            self._track_attr_write(node, parents)

    def _check_blocking(
        self, node: ast.Call, parents: Sequence[ast.AST], ctx: FileContext
    ) -> None:
        held = self._held_locks(parents)
        if not held:
            return
        callee = dotted_name(node.func)
        if callee == "time.sleep":
            self.report(
                ctx,
                node,
                f"time.sleep() while holding {held[-1]} stalls every "
                "thread contending on the lock",
            )
            return
        if not isinstance(node.func, ast.Attribute):
            return
        meth = node.func.attr
        recv = node.func.value
        recv_name = (terminal_name(recv) or "").lower()
        if meth in self._BLOCKING_METHODS:
            self.report(
                ctx,
                node,
                f"blocking I/O .{meth}() while holding {held[-1]}",
            )
        elif meth == "get" and not self._has_timeout(node):
            if any(
                recv_name == q or recv_name.endswith(q)
                for q in self._QUEUEISH
            ):
                self.report(
                    ctx,
                    node,
                    f"untimed {recv_name}.get() while holding "
                    f"{held[-1]} can block the lock forever",
                )
        elif meth == "wait" and not node.args and not node.keywords:
            # cv.wait() releases the cv's OWN lock — only waiting on a
            # different object while holding the lock is a deadlock.
            waited = dotted_name(recv) or recv_name
            if waited not in held:
                self.report(
                    ctx,
                    node,
                    f"untimed {waited}.wait() while holding {held[-1]} "
                    "(not the waited object) risks deadlock",
                )

    def _track_attr_write(
        self, node, parents: Sequence[ast.AST]
    ) -> None:
        cls, fn = self._enclosing(parents)
        if cls is None or fn is None or fn in ("__init__", "__post_init__"):
            return  # module-level or constructor writes happen-before
        targets = (
            node.targets if isinstance(node, ast.Assign) else [node.target]
        )
        in_lock = bool(self._held_locks(parents))
        for t in targets:
            if (
                isinstance(t, ast.Attribute)
                and isinstance(t.value, ast.Name)
                and t.value.id == "self"
            ):
                slot = self._attr_writes.setdefault(
                    (cls, t.attr), {"locked": None, "unlocked": None}
                )
                key = "locked" if in_lock else "unlocked"
                if slot[key] is None:
                    slot[key] = node

    def end_file(self, ctx: FileContext) -> None:
        for (cls, attr), slot in self._attr_writes.items():
            if slot["locked"] is not None and slot["unlocked"] is not None:
                self.report(
                    ctx,
                    slot["unlocked"],
                    f"{cls}.{attr} is written under a lock elsewhere "
                    f"(line {slot['locked'].lineno}) but without one "
                    "here — racy unless single-threaded by design",
                )


# ---------------------------------------------------------------------------
# env-discipline
# ---------------------------------------------------------------------------


class EnvDisciplineRule(Rule):
    """All environment reads belong in settings.py / config/.

    The reference's settings.go is the single place env vars become
    config (envconfig tags); scattering `os.environ` reads breaks the
    settings_reloader seam (runner.py re-reads settings on config
    reload — an env read elsewhere silently ignores reloads) and hides
    knobs from docs/SETTINGS parity audits.
    """

    id = "env-discipline"
    description = "os.environ read outside settings.py / config/"
    interests = (ast.Attribute, ast.Call)

    _ALLOWED_FRAGMENTS = ("settings.py", "/config/")

    def begin_file(self, ctx: FileContext) -> None:
        path = ctx.path.replace("\\", "/")
        self._exempt = any(f in path for f in self._ALLOWED_FRAGMENTS)
        self._reported_lines: Set[int] = set()

    def visit(self, node, parents, ctx: FileContext) -> None:
        if self._exempt:
            return
        hit = None
        if isinstance(node, ast.Attribute):
            if dotted_name(node) == "os.environ":
                hit = "os.environ"
        elif isinstance(node, ast.Call):
            if dotted_name(node.func) == "os.getenv":
                hit = "os.getenv"
        if hit and node.lineno not in self._reported_lines:
            self._reported_lines.add(node.lineno)
            self.report(
                ctx,
                node,
                f"{hit} outside settings.py/config/ bypasses the "
                "settings_reloader seam; add a Settings field instead",
            )


# ---------------------------------------------------------------------------
# dtype-discipline
# ---------------------------------------------------------------------------


class DtypeDisciplineRule(Rule):
    """Implicit dtype promotion in kernel scatter updates.

    `table.at[idx].add(1)` with a uint32 table promotes through JAX's
    weak-type rules and raises FutureWarning (a hard error under the
    pyproject filterwarnings, and a real error in future JAX) — but
    only when that code path RUNS.  This catches it at lint time: a
    scatter value must carry an explicit dtype (`jnp.uint32(0)`,
    `x.astype(...)`, or another array expression), never a bare Python
    numeric literal.

    Scoped to the kernel packages (ops/, models/, parallel/) where
    tables have non-default dtypes; host code doing `d.codes[i] = 1`
    on int32 numpy is fine and not scanned.
    """

    id = "dtype-discipline"
    description = "bare numeric literal in a kernel scatter update"
    interests = (ast.Call,)

    _SCATTER_METHODS = {"add", "set", "mul", "min", "max", "subtract"}
    _SCOPE_FRAGMENTS = ("/ops/", "/models/", "/parallel/")

    def begin_file(self, ctx: FileContext) -> None:
        path = ctx.path.replace("\\", "/")
        self._in_scope = any(f in path for f in self._SCOPE_FRAGMENTS)

    @staticmethod
    def _is_bare_number(node: ast.AST) -> bool:
        if isinstance(node, ast.Constant):
            return isinstance(node.value, (int, float)) and not isinstance(
                node.value, bool
            )
        if isinstance(node, ast.UnaryOp) and isinstance(
            node.op, (ast.USub, ast.UAdd)
        ):
            return DtypeDisciplineRule._is_bare_number(node.operand)
        return False

    def visit(self, node, parents, ctx: FileContext) -> None:
        if not self._in_scope or not isinstance(node, ast.Call):
            return
        # Shape: <expr>.at[<idx>].<method>(<value>)
        f = node.func
        if not (
            isinstance(f, ast.Attribute)
            and f.attr in self._SCATTER_METHODS
            and isinstance(f.value, ast.Subscript)
            and isinstance(f.value.value, ast.Attribute)
            and f.value.value.attr == "at"
        ):
            return
        if node.args and self._is_bare_number(node.args[0]):
            self.report(
                ctx,
                node,
                f".at[].{f.attr}() with a bare numeric literal "
                "promotes dtype implicitly (FutureWarning->error); "
                "wrap it, e.g. jnp.uint32(...)",
            )


# ---------------------------------------------------------------------------
# metrics-discipline
# ---------------------------------------------------------------------------


class MetricsDisciplineRule(Rule):
    """F-string-interpolated metric names: the unbounded-cardinality
    guard.

    A ``store.counter(f"...{key}...")`` mints one Counter object and
    one /metrics family PER DISTINCT VALUE of the interpolated
    expression — a per-user or per-descriptor value there grows the
    registry (and every scrape, and every statsd flush) without
    bound.  Metric names must come from a bounded set: string
    literals, ``base + ".suffix"`` over a bounded base, or the
    sanctioned interning seams (stats/manager.py's per-rule scope
    classes, which the config loader bounds), which are exempted by
    path.  Traffic-shape questions ("which key is hot?") belong to
    the hot-key sketch (observability/hotkeys.py), whose memory is
    bounded by construction.

    Flags direct f-string (and ``str.format``/percent-format)
    arguments to the StatsStore registration methods on a
    store-looking receiver.  Bounded interpolations (e.g. a lane
    index) should bind the scope to a name first — that keeps the
    bounded part visibly separate from the registration call — or
    carry a justified suppression.
    """

    id = "metrics-discipline"
    description = "interpolated metric name in a stats registration"
    interests = (ast.Call,)

    _REG_METHODS = {
        "counter",
        "gauge",
        "timer",
        "histogram",
        "counter_fn",
        "gauge_fn",
    }
    _ALLOWED_FRAGMENTS = ("stats/manager.py",)

    def begin_file(self, ctx: FileContext) -> None:
        path = ctx.path.replace("\\", "/")
        self._exempt = any(f in path for f in self._ALLOWED_FRAGMENTS)

    @staticmethod
    def _is_storeish(node: ast.AST) -> bool:
        name = terminal_name(node)
        return name is not None and name.lower().endswith("store")

    @staticmethod
    def _interpolation_kind(node: ast.AST) -> Optional[str]:
        """'f-string' / '.format()' / '%-format' when `node` builds a
        string by interpolation, else None."""
        if isinstance(node, ast.JoinedStr) and any(
            isinstance(v, ast.FormattedValue) for v in node.values
        ):
            return "f-string"
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "format"
            and isinstance(node.func.value, (ast.Constant, ast.JoinedStr))
        ):
            return ".format()"
        if (
            isinstance(node, ast.BinOp)
            and isinstance(node.op, ast.Mod)
            and isinstance(node.left, ast.Constant)
            and isinstance(node.left.value, str)
        ):
            return "%-format"
        return None

    def visit(self, node, parents, ctx: FileContext) -> None:
        if self._exempt:
            return
        f = node.func
        if not (
            isinstance(f, ast.Attribute)
            and f.attr in self._REG_METHODS
            and self._is_storeish(f.value)
        ):
            return
        if not node.args:
            return
        kind = self._interpolation_kind(node.args[0])
        if kind is not None:
            self.report(
                ctx,
                node,
                f"{kind} metric name in store.{f.attr}() mints one "
                "metric per interpolated value (unbounded "
                "cardinality); use a literal/bounded name, or the "
                "hot-key sketch for per-key questions",
            )


# ---------------------------------------------------------------------------
# timing-discipline
# ---------------------------------------------------------------------------


class TimingDisciplineRule(Rule):
    """Wall-clock reads in duration arithmetic.

    The wall clock is not monotonic: NTP slews/steps and manual sets
    make ``time.time() - t0`` go negative or jump hours — precisely
    the failure class the per-phase latency histograms, trace spans
    and anomaly detectors exist to measure honestly (observability/).
    Durations belong to ``time.perf_counter()`` / ``time.monotonic()``
    (or the injectable MonotonicClock seam, utils/time.py); wall clock
    is for TIMESTAMPS (logging, persistence, cross-process stamps).

    Flags a subtraction where either operand is a direct wall-clock
    call — ``time.time()``, ``datetime.now()``, ``datetime.utcnow()``
    (either import style) — or a name bound from one in the same
    function (or module) scope.  Additions and comparisons are
    untouched — storing or displaying wall stamps is fine.
    """

    id = "timing-discipline"
    description = "wall clock (time.time/datetime.now) in duration arithmetic"
    interests = (ast.BinOp,)

    def begin_file(self, ctx: FileContext) -> None:
        self._wall_callees = {
            "time.time",
            # `import datetime` style; datetime.now(tz) with an aware
            # tz still steps under NTP — the tz argument changes the
            # epoch, not the clock.
            "datetime.datetime.now",
            "datetime.datetime.utcnow",
        }
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ImportFrom):
                continue
            # `from time import time` makes the bare call wall-clock.
            if node.module == "time":
                if any(a.name == "time" for a in node.names):
                    self._wall_callees.add("time")
            # `from datetime import datetime [as dt]` makes
            # `datetime.now()` / `dt.utcnow()` wall-clock too.
            elif node.module == "datetime":
                for a in node.names:
                    if a.name == "datetime":
                        bound = a.asname or a.name
                        self._wall_callees.add(bound + ".now")
                        self._wall_callees.add(bound + ".utcnow")
        # scope node (FunctionDef or the Module) -> names bound from a
        # wall-clock call within it.
        self._wall_names: Dict[Optional[ast.AST], Set[str]] = {}
        self._collect_wall_names(ctx.tree)

    def _is_wall_call(self, node: ast.AST) -> bool:
        return (
            isinstance(node, ast.Call)
            and dotted_name(node.func) in self._wall_callees
        )

    def _collect_wall_names(self, tree: ast.Module) -> None:
        def scan(scope: ast.AST, body) -> None:
            # Walk WITHOUT descending into nested function defs: their
            # assignments belong to their own scope entry.
            names: Set[str] = set()
            stack = list(body)
            while stack:
                node = stack.pop()
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                if isinstance(node, ast.Assign) and self._is_wall_call(
                    node.value
                ):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            names.add(t.id)
                stack.extend(ast.iter_child_nodes(node))
            self._wall_names[scope] = names

        scan(tree, tree.body)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scan(node, node.body)

    def _is_wall(
        self, node: ast.AST, parents: Sequence[ast.AST], ctx: FileContext
    ) -> bool:
        if self._is_wall_call(node):
            return True
        if isinstance(node, ast.Name):
            fn = None
            for p in reversed(parents):
                if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    fn = p
                    break
            if node.id in self._wall_names.get(fn, ()):
                return True
            if node.id in self._wall_names.get(ctx.tree, ()):
                return True
        return False

    def visit(self, node, parents, ctx: FileContext) -> None:
        if not isinstance(node.op, ast.Sub):
            return
        if self._is_wall(node.left, parents, ctx) or self._is_wall(
            node.right, parents, ctx
        ):
            self.report(
                ctx,
                node,
                "wall clock in duration arithmetic: time.time()/"
                "datetime.now() step under NTP; use time.perf_counter()"
                "/monotonic() for durations (wall clock is for "
                "timestamps)",
            )


def _make_default_rules() -> List[Rule]:
    """Fresh rule instances (rules hold per-file state; concurrent
    engines must not share them — tests construct their own packs)."""
    return [
        JaxHostSyncRule(),
        LockDisciplineRule(),
        EnvDisciplineRule(),
        DtypeDisciplineRule(),
        TimingDisciplineRule(),
        MetricsDisciplineRule(),
    ]


# The CLI's (serial) rule pack; begin_file() resets per-file state.
DEFAULT_RULES: Sequence[Rule] = _make_default_rules()


def _make_default_project_rules():
    """The whole-program rule pack (fresh instances, same contract)."""
    from .concurrency import make_concurrency_rules
    from .contracts import make_contract_rules
    from .hotpath import make_hotpath_rules
    from .native_abi import make_native_abi_rules

    return (
        make_concurrency_rules()
        + make_contract_rules()
        + make_native_abi_rules()
        + make_hotpath_rules()
    )


DEFAULT_PROJECT_RULES = _make_default_project_rules()
