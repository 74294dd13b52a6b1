"""jaxlint rules: the six JAX-discipline checks tuned to this tree.

Each rule encodes one recurring bug class of the repo's own history
(docs/static_analysis.md carries the motivating incident per rule):

  R1  cache-key completeness  — a knob read inside an ``@lru_cache``
      jit factory that is not one of the factory's parameters cannot
      be part of the compile-cache key (ADVICE r5 #1, PR 6's hand
      re-threading).
  R2  host-sync in the hot path — ``float()/int()/bool()/.item()/
      np.asarray()/.block_until_ready()`` on a jit result inside a
      ``dispatch.timed()`` region makes a device wait masquerade as
      dispatch time (Sora's nothing-synchronizes discipline).
  R3  untimed dispatch — a cached ``_jit_*`` callable fired outside
      ``dispatch.timed()`` is invisible to the telemetry layer's
      per-site latency histograms (PR 7).
  R4  env-read hygiene — ``os.environ`` read at import time, or
      outside a designated single-reader function / the cli's
      scoped-env pattern; plus any environment WRITE outside it.
  R5  cache hygiene — ``lru_cache`` keyed on (or closing over) array
      arguments: unhashable keys at best, an unbounded per-array
      cache at worst.
  R6  geometry hygiene — a numeric literal for a known tunable
      (chunk_len / K / S / viterbi window / radix / bucket floors) at
      a jit-factory call site, or a literal ``pow2_bucket`` floor,
      bypasses `utils/geometry.Geometry` and forks the compiled
      geometry from the one its caller was given (ISSUE 16).

Jit factories are DISCOVERED (an ``@lru_cache`` def whose body calls
``jax.jit``), never hardcoded, so the rules keep covering factories
future PRs add. The designated env readers are a NAMING convention —
``*_enabled`` / ``*_mode`` / ``env_*`` / ``_check_*`` — the one-reader
discipline every knob in the tree already follows; R4 enforces that
new knobs follow it too.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set

from ziria_tpu.analysis.engine import (ENV_WRITE_METHODS, Context, Rule,
                                       env_write_target, in_timed_block,
                                       is_env_read, is_lru_cached,
                                       last_component, qual_name,
                                       subtree_contains_jit)

#: designated env single-readers (matched on the last dotted
#: component, leading underscores stripped): the ONE place a knob's
#: env default may be read, by naming convention
DESIGNATED_READER = re.compile(
    r"(_enabled$|_mode$|^env_|^check_)")

#: mode-resolver call patterns R1 refuses inside a jit factory: these
#: read process state (env / module knobs) when passed None, so a
#: factory calling one bakes an un-keyed mode into its cached program
MODE_RESOLVER = re.compile(
    r"(_enabled$|_mode$|^env_|^resolve_|^check_)")

SYNC_BUILTINS = ("float", "int", "bool")
SYNC_METHODS = ("item", "block_until_ready")
ARRAY_PULLS = ("asarray", "array")          # np.asarray(jit_result)
ARRAY_ANNOTATIONS = re.compile(
    r"(ndarray|\bArray\b|jnp\.|jax\.Array|DeviceArray)")

JIT_CALLABLE = re.compile(r"^_jit_")        # the repo's factory naming


def _jit_factories(tree: ast.Module) -> List[ast.FunctionDef]:
    """Module-level (or nested) ``@lru_cache`` defs that build jitted
    callables — the compile-cache keyed factories R1/R5 police."""
    return [n for n in ast.walk(tree)
            if isinstance(n, ast.FunctionDef)
            and is_lru_cached(n) and subtree_contains_jit(n)]


def _param_names(fn: ast.FunctionDef) -> Set[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return set(names)


class CacheKeyCompleteness(Rule):
    id = "R1"
    name = "cache-key-completeness"
    why = ("a knob read inside a jit factory body is not part of its "
           "lru_cache key: an in-process change silently reuses the "
           "stale compiled program (ADVICE r5 #1)")

    def check(self, ctx: Context) -> None:
        mod = ctx.module
        knobs = self._module_knobs(mod.tree)
        for fac in _jit_factories(mod.tree):
            params = _param_names(fac)
            for node in ast.walk(fac):
                if node is fac:
                    continue
                if is_env_read(node):
                    ctx.report(node, (
                        f"env read inside jit factory '{fac.name}' is "
                        f"not part of its compile-cache key; resolve "
                        f"in the caller and pass the value as a "
                        f"factory parameter"))
                elif isinstance(node, ast.Call):
                    name = last_component(qual_name(node.func))
                    if MODE_RESOLVER.search(name):
                        ctx.report(node, (
                            f"mode resolver '{qual_name(node.func)}' "
                            f"called inside jit factory '{fac.name}': "
                            f"the resolved mode never reaches the "
                            f"lru_cache key; resolve before keying"))
                elif isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Load) and \
                        node.id in knobs and node.id not in params:
                    ctx.report(node, (
                        f"module-level knob '{node.id}' read inside "
                        f"jit factory '{fac.name}' without being a "
                        f"factory parameter (it is mutable process "
                        f"state, not a compile-time constant)"))

    @staticmethod
    def _module_knobs(tree: ast.Module) -> Set[str]:
        """Names that behave like process-wide knobs: module-level
        assignments whose value reads the environment, plus any name
        rebound via a ``global`` statement somewhere in the module."""
        knobs: Set[str] = set()
        for node in tree.body:
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                value = node.value
                if value is not None and any(
                        is_env_read(n) for n in ast.walk(value)):
                    targets = node.targets if isinstance(
                        node, ast.Assign) else [node.target]
                    for t in targets:
                        if isinstance(t, ast.Name):
                            knobs.add(t.id)
        for node in ast.walk(tree):
            if isinstance(node, ast.Global):
                knobs.update(node.names)
        return knobs


def _device_bound_names(fn: ast.FunctionDef,
                        jit_locals: Set[str]) -> Set[str]:
    """Names in ``fn`` assigned from firing a cached jit callable —
    the values R2 treats as device-resident."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign):
            continue
        if not isinstance(node.value, ast.Call):
            continue
        if not _is_jit_dispatch(node.value, jit_locals):
            continue
        for t in node.targets:
            if isinstance(t, ast.Name):
                out.add(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                out.update(e.id for e in t.elts
                           if isinstance(e, ast.Name))
    return out


def _jit_factory_locals(fn: ast.FunctionDef) -> Set[str]:
    """Local names bound to a ``_jit_*(...)`` factory result inside
    ``fn`` (``dec = _jit_decode(...)``) — calling them is a device
    dispatch."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, ast.Call) and \
                JIT_CALLABLE.match(
                    qual_name(node.value.func).rsplit(".", 1)[-1]):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
    return out


def _class_jit_attrs(tree: ast.Module) -> Set[str]:
    """Attributes assigned ``self.X = [mod.]_jit_*(...)`` anywhere —
    ``self.X(...)`` is then a cached-jit dispatch (the StreamReceiver
    pattern)."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, ast.Call) and \
                JIT_CALLABLE.match(
                    qual_name(node.value.func).rsplit(".", 1)[-1]):
            for t in node.targets:
                if isinstance(t, ast.Attribute) and \
                        isinstance(t.value, ast.Name) and \
                        t.value.id == "self":
                    out.add(t.attr)
    return out


def _is_jit_dispatch(call: ast.Call, jit_locals: Set[str],
                     jit_attrs: Optional[Set[str]] = None) -> bool:
    """True when ``call`` fires a cached jit callable: a direct
    ``_jit_foo(...)(args)`` double call, a local bound from a
    ``_jit_*`` factory, or a ``self.attr`` bound from one."""
    f = call.func
    if isinstance(f, ast.Call):           # _jit_foo(key...)(operands)
        return bool(JIT_CALLABLE.match(
            qual_name(f.func).rsplit(".", 1)[-1]))
    if isinstance(f, ast.Name) and f.id in jit_locals:
        return True
    if jit_attrs is not None and isinstance(f, ast.Attribute) and \
            isinstance(f.value, ast.Name) and f.value.id == "self" \
            and f.attr in jit_attrs:
        return True
    return False


class HostSyncInHotPath(Rule):
    id = "R2"
    name = "host-sync-in-hot-path"
    why = ("a host sync inside a dispatch.timed() region blocks on "
           "the device there, so the per-site latency histogram "
           "reports device wait as dispatch time — and on the "
           "streaming hot loop it serializes the double buffer")

    def check(self, ctx: Context) -> None:
        mod = ctx.module
        for fn in [n for n in ast.walk(mod.tree)
                   if isinstance(n, ast.FunctionDef)]:
            jit_locals = _jit_factory_locals(fn)
            device = _device_bound_names(fn, jit_locals)
            if not (jit_locals or device):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                sync = self._sync_target(node, device, jit_locals)
                if sync is None:
                    continue
                if in_timed_block(mod, node):
                    ctx.report(node, (
                        f"host sync '{sync}' on a jit result inside a "
                        f"dispatch.timed() region: move the "
                        f"conversion out of the timed block so the "
                        f"site times the dispatch, not the device "
                        f"wait"))

    @staticmethod
    def _sync_target(call: ast.Call, device: Set[str],
                     jit_locals: Set[str]) -> Optional[str]:
        def is_device_expr(e: ast.AST) -> bool:
            if isinstance(e, ast.Name):
                return e.id in device
            if isinstance(e, (ast.Subscript, ast.Attribute)):
                return is_device_expr(e.value)
            if isinstance(e, ast.Call):
                return _is_jit_dispatch(e, jit_locals)
            return False

        f = call.func
        if isinstance(f, ast.Name) and f.id in SYNC_BUILTINS and \
                call.args and is_device_expr(call.args[0]):
            return f.id
        if isinstance(f, ast.Attribute):
            if f.attr in SYNC_METHODS and is_device_expr(f.value):
                return f".{f.attr}()"
            if f.attr in ARRAY_PULLS and call.args and \
                    is_device_expr(call.args[0]):
                q = qual_name(f)
                if q.split(".", 1)[0] in ("np", "numpy", "onp"):
                    return q
        return None


class UntimedDispatch(Rule):
    id = "R3"
    name = "untimed-dispatch"
    why = ("a cached _jit_* callable fired outside dispatch.timed() "
           "is invisible to the telemetry layer: no per-site latency "
           "histogram, no dispatch counter, no trace span")

    def check(self, ctx: Context) -> None:
        mod = ctx.module
        jit_attrs = _class_jit_attrs(mod.tree)
        for fn in [n for n in ast.walk(mod.tree)
                   if isinstance(n, ast.FunctionDef)]:
            if is_lru_cached(fn) or any(
                    isinstance(a, ast.FunctionDef) and is_lru_cached(a)
                    for a in mod.ancestors(fn)):
                continue   # a factory's inner graph fn is traced code,
                #            not a host dispatch site
            jit_locals = _jit_factory_locals(fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                if not _is_jit_dispatch(node, jit_locals, jit_attrs):
                    continue
                if not in_timed_block(mod, node):
                    name = qual_name(node.func)
                    if not name and isinstance(node.func, ast.Call):
                        name = qual_name(node.func.func) + "(...)"
                    ctx.report(node, (
                        f"cached jit callable '{name or '<call>'}' "
                        f"dispatched outside dispatch.timed(): wrap "
                        f"the call site so its latency and count are "
                        f"observable"))


class EnvReadHygiene(Rule):
    id = "R4"
    name = "env-read-hygiene"
    why = ("an env read at import time (or scattered outside a "
           "designated *_enabled/*_mode/env_* single reader) escapes "
           "the cli scoped-env pattern: the flag stops being "
           "overridable per invocation, and two readers can disagree")

    def check(self, ctx: Context) -> None:
        mod = ctx.module
        for node in ast.walk(mod.tree):
            w = env_write_target(node)
            if w is not None:
                ctx.report(w, (
                    "environment write outside the cli scoped-env "
                    "pattern: mutate os.environ only through a "
                    "scoped write+restore (runtime/cli.main)"))
                continue
            if not is_env_read(node):
                continue
            # a write's environ mention is reported above, once
            par = mod.parent_of(node)
            if isinstance(par, ast.Attribute) and \
                    par.attr in ENV_WRITE_SKIP:
                continue
            if isinstance(par, ast.Subscript) and \
                    not isinstance(par.ctx, ast.Load):
                continue       # os.environ[k] = / del: the write rule
            chain = mod.enclosing_functions(node)
            if not chain:
                ctx.report(node, (
                    "env read at import time: module import order "
                    "decides the value and the cli scoped-env "
                    "pattern cannot override it; read at call time "
                    "inside a designated single-reader function"))
            elif not any(DESIGNATED_READER.search(
                    f.name.lstrip("_")) for f in chain):
                ctx.report(node, (
                    f"env read inside "
                    f"'{chain[0].name}', which is not a designated "
                    f"single-reader (*_enabled / *_mode / env_* / "
                    f"_check_*): hoist the read into ONE reader "
                    f"function so every surface agrees on the knob"))


#: attribute accesses on environ that the write check reports — the
#: read check must not double-report their `environ` mention
ENV_WRITE_SKIP = set(ENV_WRITE_METHODS)


class CacheHygiene(Rule):
    id = "R5"
    name = "cache-hygiene"
    why = ("lru_cache keyed on (or closing over) arrays is a leak: "
           "array keys are unhashable or compare by id, so the cache "
           "grows per call and pins device buffers forever")

    def check(self, ctx: Context) -> None:
        mod = ctx.module
        cached = [n for n in ast.walk(mod.tree)
                  if isinstance(n, ast.FunctionDef) and is_lru_cached(n)]
        cached_names = {n.name for n in cached}
        for fn in cached:
            for p in fn.args.posonlyargs + fn.args.args \
                    + fn.args.kwonlyargs:
                ann = p.annotation
                if ann is not None and ARRAY_ANNOTATIONS.search(
                        ast.unparse(ann)):
                    ctx.report(p, (
                        f"lru_cache'd '{fn.name}' takes array-typed "
                        f"parameter '{p.arg}': arrays are not hashable "
                        f"cache keys — key on shape/dtype/mode "
                        f"scalars and pass the array to the returned "
                        f"callable"))
            if any(isinstance(a, ast.FunctionDef)
                   for a in mod.ancestors(fn)):
                ctx.report(fn, (
                    f"lru_cache'd '{fn.name}' is defined inside "
                    f"another function: every outer call makes a NEW "
                    f"cache closing over that call's locals (arrays "
                    f"included) — hoist the cached def to module "
                    f"scope"))
        # call-site check: obviously-array arguments to a cached
        # factory defined in this module
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if qual_name(node.func).rsplit(".", 1)[-1] \
                    not in cached_names:
                continue
            for a in list(node.args) + [k.value for k in node.keywords]:
                if isinstance(a, ast.Call):
                    q = qual_name(a.func)
                    if q.rsplit(".", 1)[-1] in ARRAY_PULLS and \
                            q.split(".", 1)[0] in ("np", "numpy",
                                                   "jnp", "jax"):
                        ctx.report(a, (
                            f"array argument "
                            f"'{ast.unparse(a)[:40]}' keys the "
                            f"lru_cache of "
                            f"'{qual_name(node.func)}': the cache "
                            f"grows one entry per array object"))


#: tunable names R6 refuses as literal keyword arguments at jit-factory
#: call sites — each has one home on the Geometry dataclass, and a
#: literal here silently forks the tree's compiled geometry
KNOWN_TUNABLES = frozenset({
    "chunk_len", "frame_len", "max_frames_per_chunk", "n_streams",
    "viterbi_window", "viterbi_radix", "min_bucket",
})


def _is_numeric_literal(node: ast.AST) -> bool:
    """A compile-time number: ``8192``, ``1 << 13``, ``-1``, or any
    BinOp/UnaryOp tree over such constants."""
    if isinstance(node, ast.Constant):
        return isinstance(node.value, (int, float)) and \
            not isinstance(node.value, bool)
    if isinstance(node, ast.BinOp):
        return _is_numeric_literal(node.left) and \
            _is_numeric_literal(node.right)
    if isinstance(node, ast.UnaryOp):
        return _is_numeric_literal(node.operand)
    return False


class GeometryHygiene(Rule):
    id = "R6"
    name = "geometry-hygiene"
    why = ("a numeric literal for a known tunable at a jit-factory "
           "call site (or a literal pow2_bucket floor) bypasses the "
           "Geometry object: the literal and Geometry's default can "
           "drift apart, and a caller's geometry never reaches that "
           "surface")

    def check(self, ctx: Context) -> None:
        mod = ctx.module
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = qual_name(node.func).rsplit(".", 1)[-1]
            if name == "pow2_bucket":
                floor = None
                if len(node.args) >= 2:
                    floor = node.args[1]
                for k in node.keywords:
                    if k.arg == "min_bucket":
                        floor = k.value
                if floor is not None and _is_numeric_literal(floor):
                    ctx.report(floor, (
                        "literal pow2_bucket floor "
                        f"'{ast.unparse(floor)}': bucket minimums live "
                        "on the Geometry object (sym_bucket / "
                        "capture_bucket / bit_bucket) — a literal here "
                        "forks the bucketing rule from the tuned "
                        "geometry"))
            elif JIT_CALLABLE.match(name):
                for k in node.keywords:
                    if k.arg in KNOWN_TUNABLES and \
                            _is_numeric_literal(k.value):
                        ctx.report(k.value, (
                            f"literal '{k.arg}="
                            f"{ast.unparse(k.value)}' at jit-factory "
                            f"call site '{qual_name(node.func)}': "
                            f"thread the value from a Geometry "
                            f"(utils/geometry) so the compile key and "
                            f"the tuned geometry cannot disagree"))


ALL_RULES = (CacheKeyCompleteness(), HostSyncInHotPath(),
             UntimedDispatch(), EnvReadHygiene(), CacheHygiene(),
             GeometryHygiene())

RULES_BY_ID = {r.id: r for r in ALL_RULES}
