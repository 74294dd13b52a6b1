"""Compiled-program observatory: what did XLA actually emit, and how
close is each dispatch site to the hardware ceiling?

The tree's perf story (ROADMAP: 4.1% of HBM peak, 0.2% of FLOPs peak
on v5e) has so far rested on hand-derived byte/FLOP formulas
(bench.py's ``_roofline``) while the compiled graphs themselves carry
the exact numbers: every jitted program exposes
``lowered.compile().cost_analysis()`` (FLOPs, bytes accessed) and
``memory_analysis()`` (argument/output/temp HBM). This module turns
those into a first-class surface — and, crucially, one that works
CPU-only, so cost attribution keeps flowing through the TPU probe
hangs that have starved BENCH since r05.

Three layers:

- **Site notes** (:func:`note_site` + :func:`observing`): each
  instrumented dispatch site (the same ``utils/dispatch.timed``
  labels the telemetry layer uses) reports the jitted callable and
  its argument avals when an :class:`Observatory` is active — free
  when idle (one truthiness check), and only shapes/dtypes are held,
  never device buffers. After a run, :meth:`Observatory.analyze`
  lowers each noted program and attributes analytical cost to its
  site label, so a measured p50 latency and an analytical byte count
  join on the label: achieved GB/s / GFLOP/s *per dispatch site*
  (`tools/rx_dispatch_bench.py` stats blocks, `tools/trace_report.py`
  via the trace's embedded ``siteCosts``).

- **Factory discovery** (:func:`discovered_factories`): the compiled
  programs live behind the tree's ``@lru_cache`` jit factories. The
  factories are DISCOVERED with jaxlint R1's convention
  (`ziria_tpu.analysis`: an ``@lru_cache`` def whose body builds a
  jitted callable), never hardcoded, and :func:`coverage` maps noted
  programs back to their factories — a factory a future PR adds shows
  up as *uncovered* in the report instead of silently missing.

- **Device peaks** (:data:`DEVICE_PEAKS`): the per-``device_kind``
  peak table that replaces bench.py's hardcoded v5e constants.
  Unknown kinds report absolute achieved numbers with the ``pct_*``
  fields omitted — absent, not wrong.

CLI: ``python -m ziria_tpu programs [--json] [--hlo-dump DIR]`` pins
the CPU backend (no TPU needed, same mechanism as bench.py's parent),
drives every dispatch surface once at a tiny geometry
(:func:`run_driver`), and prints the per-program cost table.
"""

from __future__ import annotations

import ast
import json
import os
import threading
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

# ------------------------------------------------------------ device peaks
#
# Single-chip peaks per device_kind, seeded from the v5e constants the
# bench carried since round 3 (HBM 819 GB/s, bf16 197 TFLOP/s). Keys
# are normalized device-kind strings (`_peaks_key`); an unknown kind
# yields None and every consumer then reports achieved absolutes with
# the pct_* fields omitted — never a percentage of the wrong ceiling.

DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "v5e": {"hbm_gbps": 819.0, "peak_tflops": 197.0},
}

#: observed device_kind spellings -> DEVICE_PEAKS key
_DEVICE_KIND_KEYS = {
    "tpu v5 lite": "v5e",
    "tpu v5e": "v5e",
    "tpu v5lite": "v5e",
    "v5e": "v5e",
    "v5litepod": "v5e",
}


def peaks_for(device_kind: Optional[str]) -> Optional[Dict[str, float]]:
    """The peak table entry for a ``jax.Device.device_kind`` string,
    or None when the kind is unknown (consumers must then omit the
    pct_* fields, not guess a ceiling)."""
    if not device_kind:
        return None
    k = str(device_kind).strip().lower()
    key = _DEVICE_KIND_KEYS.get(k, k if k in DEVICE_PEAKS else None)
    return DEVICE_PEAKS.get(key) if key else None


def roofline(seconds: float, bytes_accessed: Optional[float] = None,
             flops: Optional[float] = None,
             device_kind: Optional[str] = None) -> Dict[str, float]:
    """Achieved GB/s / GFLOP/s for one dispatch of a program whose
    analytical cost is (``bytes_accessed``, ``flops``) and whose
    measured latency is ``seconds`` — plus %-of-peak when the
    ``device_kind`` is in :data:`DEVICE_PEAKS`."""
    out: Dict[str, float] = {}
    if not seconds or seconds <= 0:
        return out
    peaks = peaks_for(device_kind)
    if bytes_accessed:
        gbps = bytes_accessed / seconds / 1e9
        out["achieved_gbps"] = round(gbps, 3)
        if peaks:
            out["pct_hbm_peak"] = round(100 * gbps / peaks["hbm_gbps"], 3)
    if flops:
        gflops = flops / seconds / 1e9
        out["achieved_gflops"] = round(gflops, 3)
        if peaks:
            out["pct_flops_peak"] = round(
                100 * gflops / 1e3 / peaks["peak_tflops"], 4)
    return out


# ------------------------------------------------------------ observatory


def _aval(x: Any) -> Any:
    """Shape/dtype skeleton of a call argument: arrays become
    ``jax.ShapeDtypeStruct`` (never holding the buffer), everything
    else (python scalars, tuples of scalars) passes through."""
    import jax

    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
    if isinstance(x, (tuple, list)):
        return type(x)(_aval(e) for e in x)
    return x


def _sig(avals: Tuple, kwavals: Dict) -> str:
    """Stable geometry signature for dedupe: one record per (label,
    argument geometry), however many times the site fired."""
    def one(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            return f"{getattr(a, 'dtype', '?')}{tuple(a.shape)}"
        return repr(a)

    parts = [one(a) for a in avals]
    parts += [f"{k}={one(v)}" for k, v in sorted(kwavals.items())]
    return ",".join(parts)


@dataclass
class ProgramNote:
    """One live compiled program a dispatch site reported: the jitted
    callable plus the argument geometry it was fired at."""
    label: str
    fn: Any
    avals: Tuple
    kwavals: Dict[str, Any]
    calls: int = 0

    @property
    def jit_name(self) -> Tuple[str, str]:
        """(module, qualname) of the traced python function behind the
        jitted callable — the linkage :func:`coverage` matches against
        the AST-discovered factories."""
        w = getattr(self.fn, "__wrapped__", None)
        return (getattr(w, "__module__", "") or "",
                getattr(w, "__qualname__", "") or "")


class Observatory:
    """Collects :class:`ProgramNote` entries while active (see
    :func:`observing`) and turns them into cost/memory records."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.notes: Dict[Tuple[str, str], ProgramNote] = {}

    def _note(self, label: str, fn: Any, avals: Tuple,
              kwavals: Dict[str, Any]) -> None:
        key = (label, _sig(avals, kwavals))
        with self._lock:
            n = self.notes.get(key)
            if n is None:
                n = self.notes[key] = ProgramNote(label, fn, avals,
                                                  kwavals)
            n.calls += 1

    def analyze(self, hlo_dump: Optional[str] = None) -> List[Dict]:
        """One cost/memory record per noted program (lowered and
        compiled at the noted avals — CPU-only safe). A program that
        fails to lower yields an ``error`` record instead of killing
        the sweep."""
        out = []
        for (label, sig), n in sorted(self.notes.items()):
            mod, qual = n.jit_name
            rec: Dict[str, Any] = {
                "label": label, "module": mod, "jit_qualname": qual,
                "in_avals": sig, "calls": n.calls,
            }
            try:
                rec.update(cost_of(n.fn, *n.avals, **n.kwavals))
                if hlo_dump:
                    os.makedirs(hlo_dump, exist_ok=True)
                    fname = f"{label.replace('/', '_')}_{abs(hash(sig)) & 0xffffff:06x}.hlo.txt"
                    path = os.path.join(hlo_dump, fname)
                    with open(path, "w") as f:
                        f.write(hlo_text(n.fn, *n.avals, **n.kwavals))
                    rec["hlo_path"] = path
            except Exception as e:      # pragma: no cover - backend oddity
                rec["error"] = repr(e)
            out.append(rec)
        return out

    def site_costs(self) -> Dict[str, Dict]:
        """Per-site analytical cost: the LARGEST-bytes geometry noted
        per label (the steady-state dispatch; warm-up oddities at
        smaller geometry lose). The join key for a site's measured
        p50 latency."""
        best: Dict[str, Dict] = {}
        for rec in self.analyze():
            if rec.get("error") or not rec.get("bytes_accessed"):
                continue
            cur = best.get(rec["label"])
            if cur is None or rec["bytes_accessed"] > cur["bytes_accessed"]:
                best[rec["label"]] = rec
        return best


_LOCK = threading.Lock()
_ACTIVE: Tuple[Observatory, ...] = ()


def note_site(label: str, fn: Any, *args: Any, **kwargs: Any) -> None:
    """Report a dispatch site's jitted callable + call geometry to
    every active observatory. Free when none is active (one truthiness
    check) — the hot paths carry the annotation permanently, like
    their ``dispatch.timed`` wrapper."""
    if not _ACTIVE:
        return
    avals = tuple(_aval(a) for a in args)
    kwavals = {k: _aval(v) for k, v in kwargs.items()}
    for o in _ACTIVE:
        o._note(label, fn, avals, kwavals)


@contextmanager
def observing(obs: Optional[Observatory] = None):
    """Activate an :class:`Observatory` for the block; yields it."""
    global _ACTIVE
    o = obs if obs is not None else Observatory()
    with _LOCK:
        _ACTIVE = _ACTIVE + (o,)
    try:
        yield o
    finally:
        with _LOCK:
            lst = list(_ACTIVE)
            for i in range(len(lst) - 1, -1, -1):
                if lst[i] is o:
                    del lst[i]
                    break
            _ACTIVE = tuple(lst)


# ------------------------------------------------------------ cost analysis

_COST_MEMO: Dict[Tuple[int, str], Dict] = {}


def cost_of(fn: Any, *args: Any, **kwargs: Any) -> Dict[str, float]:
    """XLA's own accounting for ONE dispatch of ``fn`` at the given
    (aval or concrete) arguments: ``flops`` and ``bytes_accessed``
    from ``cost_analysis()``, argument/output/temp HBM from
    ``memory_analysis()`` (``peak_bytes`` = their sum — the resident
    footprint of one dispatch). Memoized per (callable, geometry);
    lowering + compiling happens off the jit fast path, so the first
    call per geometry pays a compile (cheap on CPU, persistent-cached
    where enabled)."""
    avals = tuple(_aval(a) for a in args)
    kwavals = {k: _aval(v) for k, v in kwargs.items()}
    key = (id(fn), _sig(avals, kwavals))
    hit = _COST_MEMO.get(key)
    if hit is not None:
        return dict(hit)
    compiled = fn.lower(*avals, **kwavals).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    out: Dict[str, float] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }
    if ca.get("transcendentals"):
        out["transcendentals"] = float(ca["transcendentals"])
    try:
        ma = compiled.memory_analysis()
    except Exception:                    # pragma: no cover - plugin gap
        ma = None
    if ma is not None:
        arg_b = float(getattr(ma, "argument_size_in_bytes", 0) or 0)
        out_b = float(getattr(ma, "output_size_in_bytes", 0) or 0)
        tmp_b = float(getattr(ma, "temp_size_in_bytes", 0) or 0)
        out["argument_bytes"] = arg_b
        out["output_bytes"] = out_b
        out["temp_bytes"] = tmp_b
        out["peak_bytes"] = arg_b + out_b + tmp_b
    _COST_MEMO[key] = dict(out)
    return out


def hlo_text(fn: Any, *args: Any, **kwargs: Any) -> str:
    """The program's post-optimization HLO text (falls back to the
    pre-optimization lowering where the backend withholds it)."""
    avals = tuple(_aval(a) for a in args)
    kwavals = {k: _aval(v) for k, v in kwargs.items()}
    lowered = fn.lower(*avals, **kwavals)
    try:
        return lowered.compile().as_text()
    except Exception:                    # pragma: no cover - plugin gap
        return lowered.as_text()


# ------------------------------------------------------ factory discovery


def _package_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _module_name(path: str, root: str) -> str:
    """Dotted module name of a source file under the package root
    (``.../ziria_tpu/phy/wifi/rx.py`` -> ``ziria_tpu.phy.wifi.rx``)."""
    rel = os.path.relpath(path, os.path.dirname(root))
    mod = rel[:-3] if rel.endswith(".py") else rel
    mod = mod.replace(os.sep, ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


def _jit_target_names(fac: ast.FunctionDef) -> List[str]:
    """Identifier names appearing inside the arguments of the
    factory's ``*jit(...)`` calls — for a factory that jits a named
    module-level function (``jax.jit(sync_frame)``,
    ``jax.jit(jax.vmap(acquire_frame_graph))``), the traced
    function's name survives into the jitted callable's
    ``__wrapped__.__qualname__``, which is how :func:`coverage` links
    a note back here."""
    names: List[str] = []
    for node in ast.walk(fac):
        if isinstance(node, ast.Call) and isinstance(
                node.func, (ast.Name, ast.Attribute)):
            fname = (node.func.id if isinstance(node.func, ast.Name)
                     else node.func.attr)
            if fname.endswith("jit"):
                for a in node.args:
                    for sub in ast.walk(a):
                        if isinstance(sub, ast.Name):
                            names.append(sub.id)
    return names


def discovered_factories(root: Optional[str] = None) -> List[Dict]:
    """Every ``@lru_cache`` jit factory under ``root`` (default: the
    ziria_tpu package), discovered with jaxlint R1's convention
    (`analysis.rules._jit_factories`) — never a hardcoded list, so
    factories future PRs add are covered (or reported uncovered)
    automatically."""
    from ziria_tpu.analysis.engine import iter_py_files
    from ziria_tpu.analysis.rules import _jit_factories

    root = root or _package_root()
    out: List[Dict] = []
    for path in iter_py_files([root]):
        try:
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=path)
        except (OSError, SyntaxError):
            continue
        for fac in _jit_factories(tree):
            out.append({
                "module": _module_name(path, root),
                "name": fac.name,
                "line": fac.lineno,
                "jit_targets": _jit_target_names(fac),
            })
    return out


def coverage(records: List[Dict],
             factories: Optional[List[Dict]] = None) -> Dict[str, List]:
    """Map analyzed program records back to the discovered factories:
    a factory is *covered* when some record's traced function either
    is one of the factory's jit targets (``jax.jit(sync_frame)``
    style) or is defined inside the factory
    (``_jit_decode_data_mixed.<locals>.f`` style). Returns
    ``{"covered": [...], "uncovered": [...]}`` of
    ``module.name`` strings — an uncovered factory means the driver
    workloads never exercised it, i.e. a blind spot, not an error."""
    factories = discovered_factories() if factories is None else factories
    seen = [(r.get("module", ""), r.get("jit_qualname", ""))
            for r in records if not r.get("error")]
    covered, uncovered = [], []
    for fac in factories:
        fq = f"{fac['module']}.{fac['name']}"
        hit = False
        for mod, qual in seen:
            if mod != fac["module"] or not qual:
                continue
            top = qual.split(".", 1)[0]
            if qual.startswith(fac["name"] + ".<locals>") or \
                    top in fac["jit_targets"]:
                hit = True
                break
        (covered if hit else uncovered).append(fq)
    return {"covered": covered, "uncovered": uncovered}


# ------------------------------------------------------------ driver


def run_driver() -> None:
    """Exercise every instrumented dispatch surface once at a tiny
    geometry, so an active observatory sees the tree's live compiled
    programs. CPU-safe (the whole point: cost attribution must not
    need the TPU), and sized to ride the tier-1 suite's shared
    compiled geometries where possible."""
    import numpy as np

    from ziria_tpu.backend import framebatch
    from ziria_tpu.phy import channel, link
    from ziria_tpu.phy.wifi import tx

    rng = np.random.default_rng(23)
    n_bytes = 12
    rates = [6, 54]
    psdus = [rng.integers(0, 256, n_bytes).astype(np.uint8)
             for _ in rates]

    # per-frame path: encode_frame + sync/signal/decode_bucketed
    from ziria_tpu.phy.wifi import rx
    cap = np.concatenate(
        [np.zeros((50, 2), np.float32),
         np.asarray(tx.encode_frame(psdus[0], rates[0]))], axis=0)
    rx.receive(cap)

    # batched path: acquire_many + gather + decode_mixed + crc_many
    caps = [np.concatenate(
        [np.zeros((50, 2), np.float32),
         np.asarray(tx.encode_frame(p, m, add_fcs=True))], axis=0)
        for p, m in zip(psdus, rates)]
    framebatch.receive_many(caps, check_fcs=True, batched_acquire=True)

    # loopback: staged (encode_many + impair_many) and fused
    kw = dict(snr_db=30.0, cfo=1e-4, delay=12, seed=5,
              add_fcs=True, check_fcs=True)
    link.loopback_many(psdus, rates, fused=False, batched_tx=True, **kw)
    link.loopback_many(psdus, rates, fused=True, **kw)

    # per-frame channel oracle
    channel.impair_one(cap, 30.0, 1e-4, 3, 7, 0, out_len=1024)

    # single-rate batch + sweeps: encode_batch / awgn / decode_batch /
    # the one-scan BER sweep
    pb = np.stack(psdus)
    link.loopback_ber_bits(pb, rates[0], 8.0, 7)
    link.sweep_ber(pb, (rates[0],), (8.0,), (7,))

    # streaming receiver: the two fleet programs (stream_chunk_multi +
    # stream_decode_multi) over a 2-stream load at the suite's
    # canonical (K=8, 4096-chunk, 1024-window, 8-symbol) geometry
    streams, _st = link.stream_many_multi(
        [psdus[:1], psdus[1:]], [rates[:1], rates[1:]],
        snr_db=30.0, cfo=1e-4, delay=60, seed=9, add_fcs=True,
        tail=1024)
    framebatch.receive_streams(streams, chunk_len=4096, frame_len=1024,
                               max_frames_per_chunk=8, check_fcs=True)


def collect_programs(hlo_dump: Optional[str] = None,
                     driver=run_driver) -> Dict[str, Any]:
    """The one-call observatory sweep: run ``driver`` under a fresh
    observatory, analyze every noted program, and cross-check coverage
    against the AST-discovered factories. Returns the JSON-ready
    report the CLI and bench.py's ``programs`` stage share."""
    with observing() as obs:
        driver()
    records = obs.analyze(hlo_dump=hlo_dump)
    facs = discovered_factories()
    cov = coverage(records, facs)
    ok = [r for r in records if not r.get("error")]
    return {
        "programs": records,
        "programs_analyzed": len(ok),
        "factories_discovered": len(facs),
        "factories_covered": len(cov["covered"]),
        "uncovered": cov["uncovered"],
        "total_flops": round(sum(r.get("flops", 0.0) for r in ok), 1),
        "total_bytes_accessed": round(
            sum(r.get("bytes_accessed", 0.0) for r in ok), 1),
        "device_peaks": DEVICE_PEAKS,
    }


# ------------------------------------------------------------ CLI


def _format_table(report: Dict[str, Any]) -> str:
    rows = []
    for r in report["programs"]:
        if r.get("error"):
            rows.append((r["label"], r.get("in_avals", "")[:34],
                         "ERROR", r["error"][:40], "", ""))
            continue
        rows.append((
            r["label"], r.get("in_avals", "")[:34],
            f"{r.get('flops', 0):.3e}",
            f"{r.get('bytes_accessed', 0):.3e}",
            f"{r.get('peak_bytes', 0):.3e}",
            str(r.get("calls", 0)),
        ))
    w0 = max([len("label")] + [len(r[0]) for r in rows])
    w1 = max([len("in_avals")] + [len(r[1]) for r in rows])
    lines = [f"{'label':<{w0}} {'in_avals':<{w1}} {'flops':>11} "
             f"{'bytes_acc':>11} {'peak_bytes':>11} {'calls':>5}"]
    for r in rows:
        lines.append(f"{r[0]:<{w0}} {r[1]:<{w1}} {r[2]:>11} "
                     f"{r[3]:>11} {r[4]:>11} {r[5]:>5}")
    lines.append(
        f"{report['programs_analyzed']} program(s) analyzed; "
        f"{report['factories_covered']}/"
        f"{report['factories_discovered']} jit factories covered"
        + (f"; uncovered: {', '.join(report['uncovered'])}"
           if report["uncovered"] else ""))
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m ziria_tpu programs`` — the no-TPU-needed compiled
    program listing. Pins the CPU backend before first device contact
    (cost attribution must never wait on a chip) and places the
    persistent compile cache so repeat runs are cheap."""
    import argparse

    p = argparse.ArgumentParser(
        prog="ziria_tpu programs",
        description="compiled-program observatory: XLA cost/memory "
                    "attribution per jit factory, CPU-only "
                    "(docs/observability.md)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable report")
    p.add_argument("--hlo-dump", metavar="DIR", default=None,
                   help="write each program's optimized HLO text "
                        "under DIR")
    args = p.parse_args(argv)

    import jax
    try:
        # same mechanism as tests/conftest.py; a no-op (raise) when a
        # backend is already initialized in-process
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    from ziria_tpu.utils import compile_cache
    compile_cache.place()

    report = collect_programs(hlo_dump=args.hlo_dump)
    dev = jax.devices()[0]
    report["platform"] = dev.platform
    report["device_kind"] = getattr(dev, "device_kind", "?")
    # the RESOLVED single peaks entry (or null for unknown kinds), in
    # the same key tools/trace_report.py reads off exported traces —
    # so `trace_report --costs <this report>` renders %-of-peak too
    report["devicePeaks"] = peaks_for(report["device_kind"])
    if args.json:
        print(json.dumps(report))
    else:
        print(_format_table(report))
    return 0
