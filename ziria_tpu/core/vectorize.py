"""Vectorizer: scale-factor search, utility model, widening, mitigators.

Counterpart of the reference's headline optimization (SURVEY.md §2.1:
`Vectorize.hs` / `VecM.hs` / `VecSF.hs`) — there, a search over per-
component (in-width, out-width) scale factors, scored by a utility
function, rewriting `take -> takes` / `emit -> emits` and inserting
reshaping "mitigators" between mismatched widths, so the generated C
loop body is fat enough for SSE and per-item overhead is amortized.

TPU-first re-design. The knobs and their hardware meaning change:

- The SDF steady state (core/card.py) already ties the per-stage firing
  counts together via the repetition vector, so the *free* scale factor
  is ``W`` — how many steady-state iterations one fused jit step
  processes. Widths are then ``reps[k] * W`` firings per stage.
- The utility model scores W against the TPU cost structure instead of
  SSE lane width: per-step dispatch/loop overhead amortization, VPU
  lane fill (a stateless stage's firings run as one vmapped batch —
  widening is ~free until the 8x128 lanes saturate), sequential scan
  cost of stateful stages (widening buys no parallelism there), and a
  VMEM footprint cap on the live chunk.
- Widening is available BOTH as planning (pass ``W`` to
  ``backend.lower`` — no AST change) and as an explicit rewrite
  (``widen``): the take->takes analogue, where the stream item type
  changes from ``T`` to "array of w T" and every stage is rewritten to
  consume/emit blocks. ``mitigator(w_in, w_out)`` is the reshape node
  placed between stages widened by different factors.
- Pipelines with dynamic-rate stages in the middle are split into
  maximal static segments (the reference's vectorizer likewise skips
  components without static cardinalities); `backend.execute.run_vect`
  runs static segments fused under jit and bridges dynamic segments
  through the interpreter.

`VectPlan.dump()` is the ``--ddump-vect`` analogue: the scored
candidate table per segment.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from math import gcd
from typing import Dict, List, Optional, Sequence, Tuple

from ziria_tpu.core import ir
from ziria_tpu.core.card import SteadyState, TCard, cardinality, steady_state

# Model constants (relative "item-equivalents", not seconds). See the
# utility() docstring for how they enter the score. These two module
# globals are the TPU *architectural estimates*; the platform-keyed
# table below carries measured fits where calibration artifacts exist
# (VERDICT r4 next #6: constants must have a measured pedigree).
VPU_PARALLEL = 8 * 128  # one VPU tile of lanes: widening stateless work
#                         is ~free below this many parallel firings
STEP_OVERHEAD = 4096.0  # fixed per-step cost: host loop + while-loop
#                         iteration + dispatch, in item-equivalents
DEFAULT_VMEM_BUDGET = 4 << 20  # keep live chunks well under v5e's 16MB

# Per-platform utility-model constants. "measured" rows come from
# tools/calibrate_vect.py's per-regime lstsq fit (see its
# _fit_constants docstring) over committed probe tables; the TPU row
# stays an architectural estimate until a chip window lands
# VECT_CALIB.json, whose fitted_constants block model_constants()
# prefers automatically.
MODEL_CONSTANTS = {
    "tpu": {"vpu_parallel": float(VPU_PARALLEL),
            "step_overhead": STEP_OVERHEAD,
            "pedigree": "architectural estimate (one 8x128 VPU tile; "
                        "~4096 item-equivalents of dispatch); refit "
                        "pending VECT_CALIB.json"},
    "cpu": {"vpu_parallel": 18.0, "step_overhead": 20000.0,
            "pedigree": "measured: per-regime lstsq fit of "
                        "VECT_CALIB_CPU.json probe tables "
                        "(2026-07-31; vmapped work ~18x cheaper per "
                        "item than scan work, ~20k seq-item-"
                        "equivalents per-step overhead)"},
}

_CALIB_ARTIFACTS = {
    "tpu": "VECT_CALIB.json",
    "cpu": "VECT_CALIB_CPU.json",
}
_FITTED_CACHE: Dict[str, Optional[dict]] = {}


def active_platform() -> str:
    """The platform whose cost structure the plan should assume:
    "cpu" when jax is pinned to cpu (tests, --platform=cpu), else
    "tpu" (the design target)."""
    try:
        import jax
        first = (getattr(jax.config, "jax_platforms", None)
                 or "").split(",")[0].strip()
        if first == "cpu":
            return "cpu"
    except Exception:
        pass
    return "tpu"


def _fitted_from_artifact(key: str) -> Optional[dict]:
    """fitted_constants from the committed calibration artifact for
    this platform, if one exists and carries a clean fit."""
    if key in _FITTED_CACHE:
        return _FITTED_CACHE[key]
    fc = None
    try:
        import json
        import os
        root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        with open(os.path.join(root, _CALIB_ARTIFACTS[key])) as f:
            j = json.load(f)
        cand = j.get("fitted_constants") or {}
        if ("VPU_PARALLEL" in cand and "STEP_OVERHEAD" in cand
                and cand["VPU_PARALLEL"] > 0
                and cand["STEP_OVERHEAD"] > 0):
            fc = cand
    except Exception:
        fc = None
    _FITTED_CACHE[key] = fc
    return fc


def model_constants(platform: Optional[str] = None) -> dict:
    """Resolve {vpu_parallel, step_overhead, pedigree} for a platform
    (default: the active one). A fitted_constants block in the
    platform's committed calibration artifact wins over the built-in
    row, so landing VECT_CALIB.json retires the TPU guess without a
    code change."""
    plat = platform or active_platform()
    key = "cpu" if plat == "cpu" else "tpu"
    out = dict(MODEL_CONSTANTS[key])
    fc = _fitted_from_artifact(key)
    if fc:
        out.update(
            vpu_parallel=float(fc["VPU_PARALLEL"]),
            step_overhead=float(fc["STEP_OVERHEAD"]),
            pedigree=(f"measured: fitted_constants in "
                      f"{_CALIB_ARTIFACTS[key]} "
                      f"({fc.get('method', 'fit')})"))
    return out


_STATEFUL = (ir.MapAccum, ir.JaxBlock)


def _lcm(a: int, b: int) -> int:
    return a * b // gcd(a, b)


# --------------------------------------------------------------------------
# Utility model (the VecSF scoring analogue)
# --------------------------------------------------------------------------


def utility(ss: SteadyState, stages: Sequence[ir.Comp], W: int,
            item_bytes: int = 4,
            vmem_budget: int = DEFAULT_VMEM_BUDGET,
            constants: Optional[dict] = None) -> Tuple[float, str]:
    """Score scale factor W for one static segment; returns (utility, note).

    utility = items_per_step / time_proxy, where

    - items_per_step = ss.take * W (amortizes STEP_OVERHEAD);
    - each stateless stage contributes max(F/VPU_PARALLEL, 1) — its F
      firings run as one vmapped batch, so cost is flat until the VPU
      lanes fill, then linear;
    - each stateful stage contributes F — a lax.scan fires sequentially,
      so widening adds latency without parallelism (it still helps by
      amortizing the per-step overhead, which the model captures);
    - candidates whose largest live chunk exceeds vmem_budget are
      infeasible (utility -inf, note says why). Chunk size is estimated
      as the max over inter-stage edges of items-on-edge * item_bytes.

    The note string goes into the --ddump-vect style dump.
    """
    cards = [cardinality(s) for s in stages]
    # largest inter-stage edge, in items per step
    edge_items = [ss.take * W]
    run = ss.take * W
    for c, r in zip(cards, ss.reps):
        assert isinstance(c, TCard)
        run = c.o * r * W
        edge_items.append(run)
    max_edge = max(edge_items)
    bytes_live = max_edge * item_bytes
    if bytes_live > vmem_budget:
        return float("-inf"), (
            f"infeasible: live chunk {bytes_live}B > VMEM budget "
            f"{vmem_budget}B")
    c = constants or model_constants()
    time_proxy = c["step_overhead"]
    for stage, r in zip(stages, ss.reps):
        F = r * W
        if isinstance(stage, _STATEFUL):
            time_proxy += float(F)
        else:
            time_proxy += max(float(F) / c["vpu_parallel"], 1.0)
    u = (ss.take * W) / time_proxy
    return u, f"chunk={max_edge} items ({bytes_live}B)"


def search_width(ss: SteadyState, stages: Sequence[ir.Comp],
                 item_bytes: int = 4,
                 vmem_budget: int = DEFAULT_VMEM_BUDGET,
                 max_width: int = 1 << 20,
                 constants: Optional[dict] = None):
    """Enumerate candidate scale factors (powers of two) and score them.

    Returns (best_W, candidates) with candidates a list of
    (W, utility, note). Tie-break: the SMALLEST W within 1% of the best
    utility wins — beyond the amortization knee extra width only adds
    latency and memory (the reference's utility similarly penalized
    overly wide rewrites).
    """
    constants = constants or model_constants()
    cands: List[Tuple[int, float, str]] = []
    W = 1
    while W <= max_width:
        u, note = utility(ss, stages, W, item_bytes, vmem_budget,
                          constants)
        cands.append((W, u, note))
        if u == float("-inf"):
            break  # wider only grows the chunk further
        W *= 2
    best_u = max(u for _, u, _ in cands)
    if best_u == float("-inf"):
        # even W=1 blows the VMEM budget: fall back to width 1 but say so
        # in the dump rather than presenting it as a model choice
        cands.append((1, 0.0, "fallback: every candidate infeasible; "
                              "running at width 1 anyway"))
        return 1, cands
    best_W = 1
    for W, u, _ in cands:
        if u != float("-inf") and u >= 0.99 * best_u:
            best_W = W
            break
    return best_W, cands


# --------------------------------------------------------------------------
# Segmentation: maximal static runs, dynamic stages bridged
# --------------------------------------------------------------------------


@dataclass
class Segment:
    """A maximal run of consecutive pipeline stages. ``ss`` is the SDF
    steady state for static (jit-fusable) segments, None for dynamic
    segments (single stage, interpreter-executed)."""

    stages: Tuple[ir.Comp, ...]
    start: int
    ss: Optional[SteadyState]
    width: int = 1
    candidates: Tuple[Tuple[int, float, str], ...] = ()

    @property
    def dynamic(self) -> bool:
        return self.ss is None

    @property
    def comp(self) -> ir.Comp:
        return ir.pipe(*self.stages)


@dataclass
class VectPlan:
    """The vectorizer's output: segments with chosen widths."""

    segments: List[Segment] = field(default_factory=list)
    constants: dict = field(default_factory=dict)

    def dump(self) -> str:
        """--ddump-vect analogue: scored candidate table per segment."""
        lines = []
        if self.constants:
            lines.append(
                f"model constants: vpu_parallel="
                f"{self.constants['vpu_parallel']:g} step_overhead="
                f"{self.constants['step_overhead']:g} "
                f"[{self.constants['pedigree']}]")
        for i, seg in enumerate(self.segments):
            labels = " >>> ".join(s.label() for s in seg.stages)
            if seg.dynamic:
                lines.append(f"segment {i}: DYNAMIC [{labels}] -> "
                             f"interpreter (no static cardinality)")
                continue
            lines.append(
                f"segment {i}: [{labels}] reps={seg.ss.reps} "
                f"take={seg.ss.take} emit={seg.ss.emit} -> width {seg.width}")
            for W, u, note in seg.candidates:
                mark = "*" if W == seg.width else " "
                u_s = "-inf" if u == float("-inf") else f"{u:.4f}"
                lines.append(f"  {mark} W={W:<8d} utility={u_s:<10s} {note}")
        return "\n".join(lines)


def _split_static_runs(stages: Sequence[ir.Comp]):
    """Group stages into maximal runs with a combined static steady state.

    Greedy: extend the current run while ``steady_state`` of the run
    stays defined; a stage that breaks it (dynamic cardinality, or a
    rate mismatch with the run) closes the run. Dynamic single stages
    become their own segments.
    """
    runs: List[Tuple[int, List[ir.Comp], Optional[SteadyState]]] = []
    cur: List[ir.Comp] = []
    cur_start = 0
    cur_ss: Optional[SteadyState] = None
    for k, s in enumerate(stages):
        trial = steady_state(cur + [s])
        if trial is not None:
            if not cur:
                cur_start = k
            cur.append(s)
            cur_ss = trial
            continue
        if cur:
            runs.append((cur_start, cur, cur_ss))
            cur, cur_ss = [], None
        solo = steady_state([s])
        if solo is not None:
            cur, cur_start, cur_ss = [s], k, solo
        else:
            runs.append((k, [s], None))
    if cur:
        runs.append((cur_start, cur, cur_ss))
    return runs


def vectorize(comp: ir.Comp, item_bytes: int = 4,
              vmem_budget: int = DEFAULT_VMEM_BUDGET,
              max_width: int = 1 << 20) -> VectPlan:
    """Plan vectorization for a pipeline: split into segments, search a
    scale factor for each static segment. Pure planning — no IR rewrite;
    feed the plan to ``backend.execute.run_vect`` (or use a segment's
    ``width`` with ``backend.lower``)."""
    stages = ir.pipeline_stages(comp)
    plan = VectPlan()
    plan.constants = model_constants()
    for start, run, ss in _split_static_runs(stages):
        if ss is None:
            plan.segments.append(Segment(tuple(run), start, None))
            continue
        W, cands = search_width(ss, run, item_bytes, vmem_budget,
                                max_width, plan.constants)
        plan.segments.append(
            Segment(tuple(run), start, ss, W, tuple(cands)))
    return plan


# --------------------------------------------------------------------------
# Widening rewrite (take -> takes analogue) + mitigators
# --------------------------------------------------------------------------


def _widen_stateless(f, a: int, b: int, w: int):
    """Widen a per-firing function (a items -> b items) by w: the widened
    function maps a blocks of w items to b blocks of w items, applying f
    to each of the w interleaved firings via vmap. Block layout keeps raw
    stream order: block j element l is raw item j*w + l, so flattening a
    stacked (a, w, *item) input IS raw stream order."""
    import jax

    def g(xs):
        if a == 1:
            apps = xs  # (w, *item)
        else:
            flat = xs.reshape((a * w,) + xs.shape[2:])
            apps = flat.reshape((w, a) + flat.shape[1:])
        ys = jax.vmap(f)(apps)
        if b == 1:
            return ys
        flat_out = ys.reshape((w * b,) + ys.shape[2:])
        return flat_out.reshape((b, w) + flat_out.shape[1:])
    return g


def _widen_stateful(f, a: int, b: int, w: int):
    """Widen a stateful per-firing function: the w firings inside one
    widened firing run sequentially under lax.scan (state dependences
    are preserved exactly)."""
    from jax import lax

    def g(state, xs):
        if a == 1:
            apps = xs
        else:
            flat = xs.reshape((a * w,) + xs.shape[2:])
            apps = flat.reshape((w, a) + flat.shape[1:])
        state, ys = lax.scan(f, state, apps)
        if b == 1:
            return state, ys
        flat_out = ys.reshape((w * b,) + ys.shape[2:])
        return state, flat_out.reshape((b, w) + flat_out.shape[1:])
    return g


def mitigator(w_in: int, w_out: int, name: Optional[str] = None) -> ir.Comp:
    """Reshape node between stages widened by different factors — the
    reference's mitigator (SURVEY.md §2.1). Takes lcm/w_in blocks of
    w_in items, emits lcm/w_out blocks of w_out items, identity on the
    underlying item stream."""
    L = _lcm(w_in, w_out)
    a, b = L // w_in, L // w_out

    def g(xs):
        # normalize the input window to flat (L, *item) raw order;
        # width 1 means bare (unblocked) items on that side
        if w_in == 1:
            flat = xs if a > 1 else xs[None]
        elif a == 1:
            flat = xs  # one block of (w_in, *item) == (L, *item)
        else:
            flat = xs.reshape((L,) + xs.shape[2:])
        if w_out == 1:
            return flat if b > 1 else flat[0]
        if b == 1:
            return flat  # one block of (w_out, *item)
        return flat.reshape((b, w_out) + flat.shape[1:])

    return ir.Map(g, a, b, name or f"mitigate[{w_in}->{w_out}]")


def widen_stage(stage: ir.Comp, w: int) -> ir.Comp:
    """Rewrite one pipeline stage to operate on w-item blocks."""
    if w == 1:
        return stage
    if isinstance(stage, ir.Map):
        return ir.Map(_widen_stateless(stage.f, stage.in_arity,
                                             stage.out_arity, w),
                      stage.in_arity, stage.out_arity,
                      f"{stage.label()}^{w}")
    if isinstance(stage, (ir.MapAccum, ir.JaxBlock)):
        g = _widen_stateful(stage.f, stage.in_arity, stage.out_arity, w)
        if isinstance(stage, ir.MapAccum):
            adv = stage.advance
            if adv is not None:
                # one widened firing = w original firings
                def adv_w(s, n, _a=adv, _w=w):
                    return _a(s, n * _w)
            else:
                adv_w = None
            return ir.MapAccum(g, stage.init, stage.in_arity,
                               stage.out_arity, f"{stage.label()}^{w}",
                               advance=adv_w, memory=stage.memory)
        return ir.JaxBlock(g, stage.init, stage.in_arity, stage.out_arity,
                           f"{stage.label()}^{w}")
    if isinstance(stage, ir.Repeat):
        from ziria_tpu.backend.lower import firing_fn
        fire, a, b = firing_fn(stage.body)
        return ir.Map(_widen_stateless(fire, a, b, w), a, b,
                      f"repeat({stage.body.label()})^{w}")
    raise ValueError(
        f"widen_stage: stage {stage.label()} ({type(stage).__name__}) has "
        f"no static widening; leave it at width 1")


def widen(comp: ir.Comp, w, insert_mitigators: bool = True) -> ir.Comp:
    """The take->takes / emit->emits rewrite: return a pipeline over
    w-item blocks. ``w`` is an int (uniform width) or a dict mapping
    stage index -> width; with per-stage widths, mitigators are inserted
    between mismatched neighbors (when ``insert_mitigators``).

    Feeding the widened pipeline: reshape the raw stream (N, *item) to
    (N/w, w, *item); flatten the output blocks back. The test suite's
    flag matrix asserts exact agreement with the unwidened pipeline on
    both backends.
    """
    stages = ir.pipeline_stages(comp)
    if isinstance(w, int):
        widths = [w] * len(stages)
    else:
        widths = [w.get(k, 1) for k in range(len(stages))]
    out: List[ir.Comp] = []
    prev_w: Optional[int] = None
    for k, (s, wk) in enumerate(zip(stages, widths)):
        if prev_w is not None and prev_w != wk and insert_mitigators:
            out.append(mitigator(prev_w, wk))
        out.append(widen_stage(s, wk))
        prev_w = wk
    return ir.pipe(*out)
