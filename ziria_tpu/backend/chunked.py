"""Chunked state machines: compile stream-control loops to the device.

The reference compiles EVERY component — including per-sample `take`
loops with data-dependent branches — into C state machines driven by a
tick/process loop (SURVEY.md §2.1 CgComp continuations, §3.2). Round 2's
hybrid executor jitted the heavy *do-blocks* but left the loops that
walk the stream sample-by-sample (packet detection, the OFDM
symbol-gather, chunked bit emission) on the host interpreter: at 1000
bytes the receiver spent ~1.3 s firing two small jit calls per OFDM
symbol — and on a real TPU each firing is a full host round-trip.

This module is the TPU-native answer (ROADMAP r2 #2): a whole
stream-control loop (`ir.For` / `ir.While` containing takes/emits)
becomes ONE jitted **chunked masked state machine**:

- the host bulk-pulls a window of input items and ships it as a chunk;
- a `lax.while_loop` steps the loop body — takes become
  `dynamic_slice`s at a carried cursor, emits become
  `dynamic_update_slice`s into an output buffer, refs the body writes
  become loop carries (entry-pinned dtypes, the staged statement
  evaluator's discipline) — running as many iterations as fit entirely
  inside the window (guard: cursor + worst-case-take <= available);
- the step reports (iterations done, items consumed, items emitted,
  updated refs); the host flushes emissions, refills the window,
  repeats; unconsumed items are pushed back to the shared
  `interp.Source` so the enclosing stream sees them;
- at EOF the remaining iterations (at most a bound-sized sliver) run
  on the item-level interpreter, preserving exact reference EOF
  semantics — including mid-iteration upstream termination.

Host involvement drops to chunk granularity: the 1000-byte receiver
frame runs in a handful of device calls instead of ~80 — and on a real
TPU behind a host link, a handful of round-trips instead of ~80.

Safety: a loop is wrapped only when its body is *provably* stageable —
no Pipe/Repeat/Map inside, no print/error effects anywhere (they must
fire per execution, not at trace time), every comp-level expression
closure carries its source AST (`z_expr`/`z_stmts`, attached by the
elaborator), and per-iteration take/emit counts have static bounds
whose free variables the loop does not write. Anything else — and any
staging failure at runtime — falls back to the interpreter, which
remains the semantics.
"""

from __future__ import annotations

from typing import Any, List, Optional, Set

import numpy as np

from ziria_tpu.core import ir
from ziria_tpu.frontend import ast as A

# a For loop moving fewer items than this (takes+emits, whole loop)
# stays on the interpreter: jit dispatch would cost more than it saves
MIN_ITEMS_FOR = 192
# While bodies lighter than this stay interpreted (a wrapped While pays
# a compile on first execution; only sample-walking loops earn it)
MIN_WHILE_WEIGHT = 16
# unroll nested For loops below this trip count instead of fori staging
UNROLL_N = 16
# input window capacity (items) — fixed so one compile serves every
# frame length; raised per-node to cover one iteration's worst-case take
CHUNK_CAP = 4096
# emitting While loops: output-buffer budget (items) shared between the
# per-iteration emission bound and the per-chunk iteration cap — the
# step runs at most out_cap//emit_b iterations per call so emissions
# can never overflow the buffer (VERDICT r3 next #7)
WHILE_OUT_ITEMS = 65536


class _Unstageable(Exception):
    """Structural reason this subtree cannot be chunk-compiled."""


# ---------------------------------------------------------------------
# Device-step indirection: every chunk step goes through _step_call so a
# frame batcher (backend/framebatch.py) can intercept it. Single-frame
# runs call the node's jitted fn directly; under run_many each frame
# thread parks here and N lanes ride ONE vmapped call. STATS counts
# device calls either way — the unit tests' call-budget assertions and
# bench.py's call-amortization evidence both read it.

import threading as _threading

_TLS = _threading.local()
STATS = {"device_calls": 0}


def _step_call(node: "_ChunkLoop", key, args):
    b = getattr(_TLS, "batcher", None)
    if b is not None:
        return b.call(node, key, args)
    out = node._fns[key](*args)
    STATS["device_calls"] += 1   # after: a failed first trace is not a call
    return out


def step_meta(it_a, pos_a, out_n_a):
    """(it, pos, out_n) as host ints in ONE device->host transfer.
    Through a high-latency host link (~68 ms was once measured), three
    separate int() reads are three blocking round trips; stacking on
    device first makes them one. Values already on the host (batched
    fire, interpreter fallback) pass straight through."""
    if isinstance(it_a, (int, np.integer, np.ndarray)):
        return int(it_a), int(pos_a), int(out_n_a)
    import jax.numpy as jnp
    m = np.asarray(jnp.stack([jnp.asarray(it_a), jnp.asarray(pos_a),
                              jnp.asarray(out_n_a)]))
    return int(m[0]), int(m[1]), int(m[2])


class _Unboundable(_Unstageable):
    pass


# ------------------------------------------------------------ analysis


def _children(c: ir.Comp):
    if isinstance(c, ir.Bind):
        return (c.first, c.rest)
    if isinstance(c, ir.LetRef):
        return (c.body,)
    if isinstance(c, (ir.For, ir.While, ir.Repeat)):
        return (c.body,)
    if isinstance(c, ir.Branch):
        return (c.then, c.els)
    if isinstance(c, (ir.Pipe, ir.ParPipe)):
        return (c.up, c.down)
    return ()


def _walk(c: ir.Comp):
    yield c
    for ch in _children(c):
        yield from _walk(ch)


def has_stream_io(c: ir.Comp) -> bool:
    return any(isinstance(x, (ir.Take, ir.Takes, ir.Emit, ir.Emits))
               for x in _walk(c))


def _closure_ast(e) -> Optional[A.Expr]:
    """Surface AST of a comp-level Expr, if the elaborator attached it."""
    return getattr(e, "z_expr", None) if callable(e) else None


def _expr_has_effects(e: A.Expr, ctx, seen: Set[str]) -> bool:
    from ziria_tpu.backend.hybrid import _has_effects
    for x in A.iter_exprs(e):
        if isinstance(x, A.ECall):
            if x.name in ("print", "println", "error"):
                return True
            if ctx is not None and x.name in getattr(ctx, "funs", {}) \
                    and x.name not in seen:
                seen.add(x.name)
                if _has_effects(ctx.funs[x.name].decl.body, ctx, seen):
                    return True
    return False


def check_stageable(comp: ir.Comp) -> None:
    """Raise _Unstageable unless every node/closure in `comp` is the
    kind the stager knows how to trace (structure + effects only;
    runtime bounds are checked per execution)."""
    from ziria_tpu.backend.hybrid import _has_effects
    seen: Set[str] = set()
    for c in _walk(comp):
        if isinstance(c, (ir.Repeat, ir.Pipe, ir.ParPipe, ir.Map,
                          ir.MapAccum, ir.JaxBlock)):
            raise _Unstageable(f"{type(c).__name__} inside loop")
        exprs: List[Any] = []
        if isinstance(c, (ir.Emit, ir.Emits)):
            exprs.append(c.expr)
        elif isinstance(c, ir.Return):
            if callable(c.expr):
                stmts = getattr(c.expr, "z_stmts", None)
                if stmts is not None:
                    ctx = getattr(c.expr, "z_ctx", None)
                    if _has_effects(stmts, ctx, seen):
                        raise _Unstageable("print/error in do-block")
                    continue
                exprs.append(c.expr)
        elif isinstance(c, ir.LetRef):
            exprs.append(c.init)
        elif isinstance(c, ir.Assign):
            exprs.append(c.expr)
        elif isinstance(c, ir.For):
            exprs.append(c.count)
        elif isinstance(c, (ir.While, ir.Branch)):
            exprs.append(c.cond)
        for e in exprs:
            if not callable(e):
                continue  # plain constant
            ast = _closure_ast(e)
            if ast is None:
                raise _Unstageable("opaque expression closure")
            ctx = getattr(e, "z_ctx", None)
            if _expr_has_effects(ast, ctx, seen):
                raise _Unstageable("print/error in expression")


def comp_writes(comp: ir.Comp,
                shadow: frozenset = frozenset()) -> Set[str]:
    """Names of enclosing-scope refs this subtree may assign — the
    loop-carried set. Locally-declared (LetRef / bind / loop-var) names
    are shadowed out. Over-approximates through do-blocks via the
    statement-level write analysis (same as the staged evaluator)."""
    from ziria_tpu.frontend.eval import _stmt_writes
    out: Set[str] = set()
    if isinstance(comp, ir.Assign):
        if comp.var not in shadow:
            out.add(comp.var)
    elif isinstance(comp, ir.Return) and callable(comp.expr):
        stmts = getattr(comp.expr, "z_stmts", None)
        if stmts is not None:
            w: Set[str] = set()
            _stmt_writes(stmts, w)
            out |= w - shadow
    elif isinstance(comp, ir.Bind):
        out |= comp_writes(comp.first, shadow)
        sh = shadow | {comp.var} if comp.var is not None else shadow
        out |= comp_writes(comp.rest, sh)
    elif isinstance(comp, ir.LetRef):
        out |= comp_writes(comp.body, shadow | {comp.var})
    elif isinstance(comp, ir.For):
        sh = shadow | {comp.var} if comp.var is not None else shadow
        out |= comp_writes(comp.body, sh)
    elif isinstance(comp, (ir.While, ir.Repeat)):
        out |= comp_writes(comp.body, shadow)
    elif isinstance(comp, ir.Branch):
        out |= comp_writes(comp.then, shadow)
        out |= comp_writes(comp.els, shadow)
    elif isinstance(comp, (ir.Pipe, ir.ParPipe)):
        out |= comp_writes(comp.up, shadow)
        out |= comp_writes(comp.down, shadow)
    else:
        orig = getattr(comp, "orig", None)
        if orig is not None:
            out |= comp_writes(orig, shadow)
    return out


def _count_bound(count, env: ir.Env, wset: Set[str]) -> int:
    """Evaluate a nested loop count against the ENTRY env. Only safe if
    the wrapped region never writes the count's free variables."""
    if not callable(count):
        return int(count)
    ast = _closure_ast(count)
    if ast is None:
        raise _Unboundable("opaque count")
    from ziria_tpu.frontend.elab import free_vars
    if free_vars(ast) & wset:
        raise _Unboundable("count depends on loop-written state")
    return int(ir.eval_expr(count, env))


def take_bound(comp: ir.Comp, env: ir.Env, wset: Set[str]) -> int:
    """Max items one execution of `comp` can take (static per entry)."""
    if isinstance(comp, ir.Take):
        return 1
    if isinstance(comp, ir.Takes):
        return comp.n
    if isinstance(comp, ir.Bind):
        return (take_bound(comp.first, env, wset)
                + take_bound(comp.rest, env, wset))
    if isinstance(comp, ir.LetRef):
        return take_bound(comp.body, env, wset)
    if isinstance(comp, ir.Branch):
        return max(take_bound(comp.then, env, wset),
                   take_bound(comp.els, env, wset))
    if isinstance(comp, ir.For):
        b = take_bound(comp.body, env, wset)
        if b == 0:
            return 0
        return max(0, _count_bound(comp.count, env, wset)) * b
    if isinstance(comp, ir.While):
        if has_stream_io(comp.body):
            raise _Unboundable("stream I/O inside nested while")
        return 0
    orig = getattr(comp, "orig", None)
    if orig is not None:
        return take_bound(orig, env, wset)
    return 0


def emit_bound(comp: ir.Comp, env: ir.Env, wset: Set[str]) -> int:
    if isinstance(comp, ir.Emit):
        return 1
    if isinstance(comp, ir.Emits):
        return comp.n
    if isinstance(comp, ir.Bind):
        return (emit_bound(comp.first, env, wset)
                + emit_bound(comp.rest, env, wset))
    if isinstance(comp, ir.LetRef):
        return emit_bound(comp.body, env, wset)
    if isinstance(comp, ir.Branch):
        return max(emit_bound(comp.then, env, wset),
                   emit_bound(comp.els, env, wset))
    if isinstance(comp, ir.For):
        b = emit_bound(comp.body, env, wset)
        if b == 0:
            return 0
        return max(0, _count_bound(comp.count, env, wset)) * b
    if isinstance(comp, ir.While):
        if has_stream_io(comp.body):
            raise _Unboundable("stream I/O inside nested while")
        return 0
    orig = getattr(comp, "orig", None)
    if orig is not None:
        return emit_bound(orig, env, wset)
    return 0


def _body_weight(comp: ir.Comp) -> int:
    """Rough op weight of a loop body (for the wrap/no-wrap gate)."""
    from ziria_tpu.backend.hybrid import _stmts_weight
    w = 0
    for c in _walk(comp):
        w += 1
        if isinstance(c, ir.Return) and callable(c.expr):
            stmts = getattr(c.expr, "z_stmts", None)
            if stmts is not None:
                w += _stmts_weight(stmts)
    return w


# ------------------------------------------------------------ stager


class _St:
    """Mutable staging state threaded through one traced step.

    `spy`, when set, records emitted item values instead of writing the
    output buffer — the trace-time discovery pass that learns the
    emission dtype/shape before the real while_loop is built (its dead
    traced ops are DCE'd by XLA).
    """

    __slots__ = ("chunk", "pos", "out_buf", "out_n", "spy")

    def __init__(self, chunk, pos, out_buf, out_n, spy=None):
        self.chunk = chunk
        self.pos = pos
        self.out_buf = out_buf
        self.out_n = out_n
        self.spy = spy


def _is_traced_val(v) -> bool:
    from ziria_tpu.frontend.eval import _is_traced
    return _is_traced(v)


def _stage(comp: ir.Comp, env: ir.Env, st: _St):
    """Trace one execution of `comp` under jax. Returns its value."""
    import jax.numpy as jnp
    from jax import lax

    orig = getattr(comp, "orig", None)
    if orig is not None:               # nested _ChunkLoop: stage inline
        return _stage(orig, env, st)

    if isinstance(comp, ir.Take):
        x = lax.dynamic_index_in_dim(st.chunk, st.pos, 0, keepdims=False)
        st.pos = st.pos + 1
        return x

    if isinstance(comp, ir.Takes):
        xs = lax.dynamic_slice_in_dim(st.chunk, st.pos, comp.n, 0)
        st.pos = st.pos + comp.n
        return xs

    if isinstance(comp, ir.Emit):
        v = jnp.asarray(ir.eval_expr(comp.expr, env))
        if st.spy is not None:
            st.spy.append(v)
            return None
        st.out_buf = lax.dynamic_update_slice_in_dim(
            st.out_buf, v[None].astype(st.out_buf.dtype), st.out_n, 0)
        st.out_n = st.out_n + 1
        return None

    if isinstance(comp, ir.Emits):
        v = jnp.asarray(ir.eval_expr(comp.expr, env))
        if st.spy is not None:
            st.spy.append(v[0])
            return None
        st.out_buf = lax.dynamic_update_slice_in_dim(
            st.out_buf, v.astype(st.out_buf.dtype), st.out_n, 0)
        st.out_n = st.out_n + comp.n
        return None

    if isinstance(comp, ir.Return):
        return ir.eval_expr(comp.expr, env)

    if isinstance(comp, ir.Bind):
        v = _stage(comp.first, env, st)
        if comp.var is not None:
            env = env.child()
            env.bind(comp.var, v)
        return _stage(comp.rest, env, st)

    if isinstance(comp, ir.LetRef):
        env = env.child()
        env.bind_ref(comp.var, ir.eval_expr(comp.init, env))
        return _stage(comp.body, env, st)

    if isinstance(comp, ir.Assign):
        env.set(comp.var, ir.eval_expr(comp.expr, env))
        return None

    if isinstance(comp, ir.Branch):
        pred = ir.eval_expr(comp.cond, env)
        if not _is_traced_val(pred):
            return _stage(comp.then if bool(pred) else comp.els, env, st)
        return _staged_branch(comp, pred, env, st)

    if isinstance(comp, ir.For):
        n = ir.eval_expr(comp.count, env)
        if not _is_traced_val(n) and int(n) <= UNROLL_N:
            v = None
            for i in range(int(n)):
                e = env
                if comp.var is not None:
                    e = env.child()
                    e.bind(comp.var, i)
                v = _stage(comp.body, e, st)
            return v
        return _staged_loop(comp.body, env, st, var=comp.var,
                            n=n, cond=None)

    if isinstance(comp, ir.While):
        return _staged_loop(comp.body, env, st, var=None,
                            n=None, cond=comp.cond)

    raise _Unstageable(f"cannot stage {type(comp).__name__}")


def _resolves_ref(env: ir.Env, name: str) -> bool:
    e = env
    while e is not None:
        if name in e._refs:
            return True
        if name in e._vars:
            return False
        e = e._parent
    return False


def _carry_refs(comp: ir.Comp, env: ir.Env) -> List[str]:
    """Written ref names that resolve in `env` (outer carries), in a
    deterministic order. Names that resolve to immutable binds (or
    nothing) are body-local declarations — not carried."""
    return [n for n in sorted(comp_writes(comp))
            if _resolves_ref(env, n)]


def _pin(vals):
    """jnp-ify and remember dtypes (entry-pinned, like _staged_for)."""
    import jax.numpy as jnp
    arrs = [jnp.asarray(v) for v in vals]
    return arrs, [a.dtype for a in arrs]


def _staged_branch(comp: ir.Branch, pred, env: ir.Env, st: _St):
    import jax.numpy as jnp
    from jax import lax

    if st.spy is not None:
        # discovery pass: trace both arms eagerly (no cond needed —
        # the ops are dead, only the recorded emission avals matter)
        _stage(comp.then, env, st)
        _stage(comp.els, env, st)
        return None

    io = has_stream_io(comp)
    names = _carry_refs(comp, env)
    vals0, dts = _pin([env.lookup(n) for n in names])
    with_out = io and st.out_buf is not None
    oper = (st.pos,
            st.out_n if with_out else jnp.int32(0),
            st.out_buf if with_out else jnp.int32(0),
            tuple(vals0))

    def arm(body):
        def f(op):
            pos, out_n, out_buf, vals = op
            st2 = _St(st.chunk, pos,
                      out_buf if with_out else st.out_buf,
                      out_n if with_out else st.out_n)
            for n, v in zip(names, vals):
                env.set(n, v)
            v = _stage(body, env, st2)
            if v is not None:
                raise _Unstageable("Branch arm value with traced "
                                   "condition")
            outv = tuple(jnp.asarray(env.lookup(n)).astype(dt)
                         for n, dt in zip(names, dts))
            return (st2.pos,
                    st2.out_n if with_out else jnp.int32(0),
                    st2.out_buf if with_out else jnp.int32(0),
                    outv)
        return f

    res = lax.cond(jnp.asarray(pred), arm(comp.then), arm(comp.els), oper)
    st.pos = res[0]
    if with_out:
        st.out_n, st.out_buf = res[1], res[2]
    for n, v in zip(names, res[3]):
        env.set(n, v)
    return None


def _staged_loop(body: ir.Comp, env: ir.Env, st: _St,
                 var: Optional[str], n, cond):
    """Nested For (traced or large count) / While as lax.while_loop."""
    import jax.numpy as jnp
    from jax import lax

    if st.spy is not None:
        # discovery pass: one body iteration records the emission avals
        e = env
        if var is not None:
            e = env.child()
            e.bind(var, jnp.int32(0))
        _stage(body, e, st)
        return None

    io = has_stream_io(body)
    names = _carry_refs(body, env)
    if cond is not None:
        # mutable refs the condition reads must ride the carry too
        ast = _closure_ast(cond)
        if ast is None:
            raise _Unstageable("opaque nested while condition")
        from ziria_tpu.frontend.elab import free_vars
        names = names + [m for m in sorted(free_vars(ast))
                         if m not in names and _resolves_ref(env, m)]
    vals0, dts = _pin([env.lookup(m) for m in names])
    with_out = io and st.out_buf is not None

    carry0 = (jnp.int32(0), st.pos,
              st.out_n if with_out else jnp.int32(0),
              st.out_buf if with_out else jnp.int32(0),
              tuple(vals0))

    def put(vals):
        for m, v in zip(names, vals):
            env.set(m, v)

    def cond_fn(carry):
        i, pos, out_n, out_buf, vals = carry
        if cond is None:
            return i < jnp.asarray(n, jnp.int32)
        put(vals)
        return jnp.asarray(ir.eval_expr(cond, env), bool)

    def body_fn(carry):
        i, pos, out_n, out_buf, vals = carry
        put(vals)
        st2 = _St(st.chunk, pos,
                  out_buf if with_out else st.out_buf,
                  out_n if with_out else st.out_n)
        e = env
        if var is not None:
            e = env.child()
            e.bind(var, i)
        v = _stage(body, e, st2)
        if v is not None:
            raise _Unstageable("loop body value used across iterations")
        outv = tuple(jnp.asarray(env.lookup(m)).astype(dt)
                     for m, dt in zip(names, dts))
        return (i + 1, st2.pos,
                st2.out_n if with_out else jnp.int32(0),
                st2.out_buf if with_out else jnp.int32(0), outv)

    res = lax.while_loop(cond_fn, body_fn, carry0)
    st.pos = res[1]
    if with_out:
        st.out_n, st.out_buf = res[2], res[3]
    put(res[4])
    return None


# ------------------------------------------------------------ the node


def _bucket(n: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return b


# write-back policy (shared with _JitDo): small leaves become numpy —
# the interpreter's per-item fast path — while buffers over this many
# elements stay device-resident for the next jit block
HOST_SMALL_MAX = 4096


class _ChunkLoop(ir.Comp):
    """A For/While stream-control loop compiled as a chunked state
    machine. Executed by the interpreter through the `run_gen` hook;
    every structural failure falls back to interpreting `self.orig`
    (the oracle semantics). Post-compile runtime errors re-raise — a
    silent demotion would hide real bugs (ADVICE r2)."""

    def __init__(self, orig: ir.Comp):
        object.__setattr__(self, "orig", orig)
        object.__setattr__(self, "_fns", {})
        object.__setattr__(self, "_steps", {})
        object.__setattr__(self, "_ok_keys", set())
        object.__setattr__(self, "_broken", False)
        object.__setattr__(self, "_fb", None)

    def _fallback_comp(self) -> ir.Comp:
        """Interpreter fallback still deserves jitted do-blocks: a loop
        below the chunking threshold must not run slower than the plain
        hybrid executor would have run it."""
        if self._fb is None:
            from ziria_tpu.backend.hybrid import hybridize
            object.__setattr__(
                self, "_fb", hybridize(self.orig, chunk_loops=False))
        return self._fb

    def label(self) -> str:
        return f"ChunkLoop({self.orig.label()})"

    # ---------------------------------------------------- jit step

    def _get_fn(self, struct, names, take_b: int, out_cap: int,
                is_for: bool, var, iter_cap: int = 0):
        import jax
        import jax.numpy as jnp
        from ziria_tpu.backend.hybrid import _env_rebuild
        from ziria_tpu.frontend.externals import viterbi_mode

        # the staged viterbi_soft ext reads ZIRIA_VITERBI_WINDOW /
        # ZIRIA_VITERBI_METRIC at trace time, so the decode mode is
        # part of this trace's identity: fold it into the cache key so
        # an in-process env change re-traces instead of silently
        # reusing the old mode (ADVICE r5 #1)
        key = (struct, tuple(names), take_b, out_cap, is_for, iter_cap,
               viterbi_mode())
        fn = self._fns.get(key)
        if fn is not None:
            return key, fn

        body = self.orig.body
        cond = self.orig.cond if isinstance(self.orig, ir.While) else None

        def step(chunk, avail, n, it0, vals):
            env = _env_rebuild(struct, list(vals))
            rvals0, dts = _pin([env.lookup(m) for m in names])

            if out_cap:
                # discovery pass: learn the emitted item aval by staging
                # one throwaway iteration on a fresh env (ops are dead,
                # XLA DCEs them)
                spy: List[Any] = []
                env_spy = _env_rebuild(struct, list(vals))
                st_spy = _St(chunk, jnp.int32(0), None, None, spy=spy)
                e = env_spy
                if var is not None:
                    e = env_spy.child()
                    e.bind(var, jnp.int32(0))
                _stage(body, e, st_spy)
                if not spy:
                    raise _Unstageable("emit bound > 0 but no emission "
                                       "site reached in discovery")
                item = spy[0]
                dt = jnp.result_type(*spy) if len(spy) > 1 else item.dtype
                for s in spy:
                    if jnp.shape(s) != jnp.shape(item):
                        raise _Unstageable("emission shapes disagree")
                out_buf0 = jnp.zeros((out_cap,) + jnp.shape(item), dt)
            else:
                out_buf0 = jnp.int32(0)

            def put(vals_):
                for m, v in zip(names, vals_):
                    env.set(m, v)

            def cond_fn(carry):
                it, pos, out_n, out_buf, rvals = carry
                fits = pos + take_b <= avail
                if is_for:
                    return jnp.logical_and(it < n, fits)
                put(rvals)
                c = jnp.asarray(ir.eval_expr(cond, env), bool)
                if iter_cap:
                    # emitting While: stop before the output buffer
                    # can overflow; the host flushes and re-enters
                    c = jnp.logical_and(c, it - it0 < iter_cap)
                return jnp.logical_and(c, fits)

            def body_fn(carry):
                it, pos, out_n, out_buf, rvals = carry
                put(rvals)
                st = _St(chunk, pos,
                         out_buf if out_cap else None,
                         out_n if out_cap else None)
                e = env
                if var is not None:
                    e = env.child()
                    e.bind(var, it)
                v = _stage(body, e, st)
                if v is not None:
                    raise _Unstageable("loop body value is used")
                outv = tuple(jnp.asarray(env.lookup(m)).astype(d)
                             for m, d in zip(names, dts))
                return (it + 1, st.pos,
                        st.out_n if out_cap else jnp.int32(0),
                        st.out_buf if out_cap else jnp.int32(0), outv)

            carry = (it0, jnp.int32(0), jnp.int32(0), out_buf0,
                     tuple(rvals0))
            return jax.lax.while_loop(cond_fn, body_fn, carry)

        fn = jax.jit(step)
        # _steps must be visible before _fns: a concurrent frame thread
        # that sees the cached fn may immediately park a request whose
        # batched fire reads _steps[key]
        self._steps[key] = step
        self._fns[key] = fn
        return key, fn

    # ---------------------------------------------------- driver

    def run_gen(self, env: ir.Env, source, xp=np):
        from ziria_tpu.interp.interp import Source, _run

        orig = self.orig
        is_for = isinstance(orig, ir.For)

        def fallback():
            return _run(self._fallback_comp(), env, source, xp)

        if self._broken or not isinstance(source, Source):
            return (yield from fallback())

        # ---- per-execution bounds & the is-it-worth-it gate
        try:
            wset = comp_writes(orig.body)
            take_b = take_bound(orig.body, env, wset)
            emit_b = emit_bound(orig.body, env, wset)
            if is_for:
                n = int(ir.eval_expr(orig.count, env))
                if n <= 0:
                    return None
                if n * (take_b + emit_b) < MIN_ITEMS_FOR:
                    return (yield from fallback())
                out_cap = _bucket(n * emit_b) if emit_b else 0
            else:
                n = 0
                if emit_b:
                    # bound emissions per chunk by capping iterations:
                    # the step stops after iter_cap body iterations (or
                    # when the condition/input guard stops it), reports
                    # its counts, and the host re-enters — a
                    # detect-then-emit While runs fully chunked
                    iter_cap = WHILE_OUT_ITEMS // emit_b
                    if iter_cap < 1:
                        raise _Unstageable("while emission bound "
                                           "exceeds the output budget")
                    iter_cap = min(iter_cap, 2048)
                    out_cap = _bucket(emit_b * iter_cap)
                else:
                    out_cap = 0
        except _Unstageable:
            return (yield from fallback())

        import jax.numpy as jnp
        from ziria_tpu.backend.hybrid import _env_signature

        if is_for or not emit_b:
            iter_cap = 0
        cap = max(CHUNK_CAP, _bucket(take_b)) if take_b else 0
        if is_for and take_b:
            cap = min(cap, _bucket(max(1, n * take_b)))
            cap = max(cap, _bucket(take_b))

        try:
            struct, vals = _env_signature(env)
            names = _carry_refs(orig.body, env)
            if not is_for:
                ast = _closure_ast(orig.cond)
                if ast is None and callable(orig.cond):
                    raise _Unstageable("opaque while condition")
                if ast is not None:
                    from ziria_tpu.frontend.elab import free_vars
                    names = names + [
                        m for m in sorted(free_vars(ast))
                        if m not in names and _resolves_ref(env, m)]
            key, _ = self._get_fn(struct, names, take_b, out_cap,
                                  is_for, orig.var if is_for else None,
                                  iter_cap)
        except _Unstageable:
            return (yield from fallback())

        name_idx = {}
        # vals indices of carried names, for updating between steps
        flat_names: List[str] = []
        for (vnames, rnames, _w) in struct:
            flat_names.extend(vnames)
            flat_names.extend(rnames)
        for m in names:
            # innermost occurrence wins (matches Env.set semantics)
            for i in range(len(flat_names) - 1, -1, -1):
                if flat_names[i] == m:
                    name_idx[m] = i
                    break

        vals = list(vals)
        it = 0
        buf: List[Any] = []
        eof = False

        def host_cond() -> bool:
            if is_for:
                return it < n
            return bool(ir.eval_expr(orig.cond, env))

        def write_back(final: bool) -> None:
            wvals = [vals[name_idx[m]] for m in names]
            if final:
                # ALL small leaves come back in one device_get instead
                # of a blocking read per leaf (each a host-link round
                # trip); big buffers stay device-resident
                import jax
                small = [i for i, v in enumerate(wvals)
                         if getattr(v, "size", 0) <= HOST_SMALL_MAX]
                if small:
                    got = jax.device_get([wvals[i] for i in small])
                    for i, g in zip(small, got):
                        wvals[i] = np.asarray(g)
            for m, v in zip(names, wvals):
                env.set(m, v)

        while host_cond():
            if take_b:
                need = cap if not is_for else min(cap, (n - it) * take_b)
                if not eof and len(buf) < need:
                    got, eof = source.pull_block(need - len(buf))
                    buf.extend(got)
                if len(buf) < take_b:
                    # not enough input for even one worst-case
                    # iteration: run ONE iteration on the interpreter
                    # (exact EOF semantics — it may consume fewer than
                    # the bound, or legitimately raise UpstreamDone out
                    # of this loop)
                    source.push_back(buf)
                    buf = []
                    e = env
                    if is_for and orig.var is not None:
                        e = env.child()
                        e.bind(orig.var, it)
                    yield from _run(self._fallback_comp().body, e,
                                    source, xp)
                    # the interpreter mutated carried refs directly in
                    # env; refresh vals so a later chunk step (or the
                    # final/fallback write_back) doesn't clobber them
                    # with stale pre-tail device values
                    for m in names:
                        vals[name_idx[m]] = env.lookup(m)
                    it += 1
                    continue

            if take_b:
                avail = min(len(buf), cap)
                chunk = np.stack([np.asarray(x) for x in buf[:cap]])
                if chunk.shape[0] < cap:
                    pad = np.zeros((cap - chunk.shape[0],)
                                   + chunk.shape[1:], chunk.dtype)
                    chunk = np.concatenate([chunk, pad], axis=0)
            else:
                avail = 0
                chunk = np.zeros((1,), np.int32)

            try:
                it_a, pos_a, out_n_a, out_buf_a, rvals_a = _step_call(
                    self, key,
                    (jnp.asarray(chunk), jnp.int32(avail), jnp.int32(n),
                     jnp.int32(it), tuple(vals)))
                self._ok_keys.add(key)
            except Exception:
                if key in self._ok_keys:
                    raise  # runtime error after a proven compile: do
                    #        not mask it behind a silent slow path
                # first-trace failure: permanent structural fallback
                object.__setattr__(self, "_broken", True)
                source.push_back(buf)
                write_back(final=True)
                return (yield from fallback())

            new_it, consumed, out_k = step_meta(it_a, pos_a, out_n_a)
            for m, v in zip(names, rvals_a):
                vals[name_idx[m]] = v
            write_back(final=False)

            if out_cap:
                k = out_k
                if k:
                    flush = np.asarray(out_buf_a[:k])
                    for row in flush:
                        yield row
            if consumed:
                buf = buf[consumed:]
            progress = new_it > it or consumed > 0
            it = new_it
            if is_for and it >= n:
                break
            if not progress and take_b and len(buf) >= take_b:
                # guard said an iteration fits but none ran — a stager
                # bug; surface it rather than spin
                raise RuntimeError(
                    f"chunked loop made no progress with {len(buf)} "
                    f"items buffered (take_bound={take_b})")
            # else: insufficient buffered input; the next round pulls
            # more or enters the interpreter tail path

        source.push_back(buf)
        write_back(final=True)
        return None


def wrap_loops(comp: ir.Comp, dump=None) -> ir.Comp:
    """Walk `comp`, replacing stageable stream-I/O For/While loops with
    _ChunkLoop nodes (called from backend.hybrid.hybridize)."""

    def walk(c: ir.Comp) -> ir.Comp:
        if isinstance(c, (ir.For, ir.While)) and has_stream_io(c.body):
            try:
                check_stageable(c.body)
                if isinstance(c, ir.While):
                    if callable(c.cond):
                        ast = _closure_ast(c.cond)
                        if ast is None:
                            raise _Unstageable("opaque while condition")
                        if _expr_has_effects(ast, getattr(c.cond, "z_ctx",
                                                          None), set()):
                            raise _Unstageable("effects in while "
                                               "condition")
                    if _body_weight(c.body) < MIN_WHILE_WEIGHT:
                        raise _Unstageable("while body too light")
                node = _ChunkLoop(
                    ir.map_children(c, lambda ch, _b: walk(ch)))
                if dump is not None:
                    dump(f"  chunked {c.label()}")
                return node
            except _Unstageable as e:
                if dump is not None:
                    dump(f"  loop {c.label()} stays interpreted: {e}")
        return ir.map_children(c, lambda ch, _b: walk(ch))

    return walk(comp)
