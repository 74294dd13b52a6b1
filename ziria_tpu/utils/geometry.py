"""The ONE declarative geometry object behind every jit factory.

Fifteen PRs hand-picked this tree's equivalents of the paper's
vectorization widths — ``chunk_len`` (the streaming window),
``max_frames_per_chunk`` (K), ``n_streams`` (S, the fleet width), the
power-of-two bucket floors (symbol 4 / capture 512 / TX bit 128), the
detector parameters, the Viterbi ``(window, metric, radix)`` triple,
``fused_demap``, ``sco_track`` — as constants scattered across call
sites, env knobs, and compile-cache keys. :class:`Geometry` folds all
of them into one frozen, hashable dataclass:

- **Defaults are today's constants.** ``Geometry()`` resolves to
  exactly the values every surface used before this module existed,
  so the default object is a no-op by construction: zero new compiled
  programs (``dispatch.no_recompile`` pins this in
  tests/test_geometry.py), identical checkpoint geometry
  fingerprints, identical emissions bit for bit.
- **resolve() folds CLI/env knobs exactly once.** The ``None``-valued
  decode-mode fields (viterbi window/metric/radix, fused_demap,
  sco_track) mean "read the env default"; :meth:`Geometry.resolve`
  replaces them with concrete values through this module's designated
  single-readers (``env_*`` — jaxlint R4's naming convention), and
  the resolved values are what the jit-factory caches key on. The
  legacy readers (``rx.sco_track_enabled``, ``rx.fused_demap_enabled``,
  ``externals.viterbi_mode``, ``viterbi._check_radix``) all delegate
  here, so each knob still has ONE env read in the whole tree.
- **The factories keep their scalar keys.** A ``Geometry`` is the
  *source* of the cache key, not the key object itself: drivers and
  constructors (``StreamReceiver``, ``MultiStreamReceiver``,
  ``ServeConfig``, ``link.loopback_many``, ``rx.receive``) accept a
  ``geometry=`` and derive the exact scalar tuples the ``_jit_*``
  factories cache on. Two geometries that agree on a factory's knobs
  share its compiled program (another ``chunk_len`` never forks the
  decode caches), and data-dependent buckets (``n_sym_bucket`` from
  an input's length) stay derived-per-call through the bucket *rules*
  this object owns (:meth:`sym_bucket` / :meth:`capture_bucket` /
  :meth:`bit_bucket` — jaxlint R6 flags literal floors at call
  sites).

jax-free by design (like runtime/serve and utils/telemetry): the
geometry must be constructible, resolvable, and serializable through
TPU probe hangs — ``tools/geometry_smoke.py`` is the precommit gate
for exactly that.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, Optional

from ziria_tpu.utils.dispatch import pow2_bucket

#: valid Viterbi metric dtypes — ops/viterbi.METRIC_DTYPES aliases
#: this tuple, so the validation set cannot drift from the kernels
VITERBI_METRICS = ("float32", "int16", "int8")
#: valid Viterbi ACS radixes — ops/viterbi.RADIXES aliases this
VITERBI_RADIXES = (2, 4)


# --------------------------------------------------- designated env readers
#
# jaxlint R4 allows env reads only inside designated single-reader
# functions (the `env_*`/`*_enabled`/`*_mode`/`check_*` naming
# convention). These are THE readers of the geometry knobs' env
# defaults; every legacy reader elsewhere in the tree delegates here.


def env_viterbi_window() -> int:
    """ZIRIA_VITERBI_WINDOW: sliding-window decode length, 0 = off.
    An unparseable value degrades to 0 (off, the safe default) —
    externals.viterbi_mode's long-standing contract."""
    try:
        return int(os.environ.get("ZIRIA_VITERBI_WINDOW", "0"))
    except ValueError:
        return 0


def env_viterbi_metric() -> str:
    """ZIRIA_VITERBI_METRIC: ACS metric dtype (default float32). An
    unknown metric raises — the quantized kernels are an opt-in
    accuracy trade that must never be silently dropped."""
    md = os.environ.get("ZIRIA_VITERBI_METRIC") or "float32"
    if md not in VITERBI_METRICS:
        raise ValueError(
            f"ZIRIA_VITERBI_METRIC={md!r} is not one of "
            f"{VITERBI_METRICS}")
    return md


def env_viterbi_radix() -> int:
    """ZIRIA_VITERBI_RADIX: ACS radix (default 2, the oracle). An
    unknown radix raises — an opt-in kernel rewrite must never be
    silently dropped."""
    raw = os.environ.get("ZIRIA_VITERBI_RADIX") or "2"
    try:
        radix = int(raw)
    except ValueError:
        raise ValueError(
            f"ZIRIA_VITERBI_RADIX={raw!r} is not one of "
            f"{VITERBI_RADIXES}")
    if radix not in VITERBI_RADIXES:
        raise ValueError(
            f"ZIRIA_VITERBI_RADIX={radix!r} is not one of "
            f"{VITERBI_RADIXES}")
    return radix


def env_fused_demap() -> bool:
    """ZIRIA_FUSED_DEMAP (default OFF — the XLA front end is the
    oracle): run demap+deinterleave+depuncture as an in-kernel
    prologue of the Pallas ACS, on BOTH the known-rate decode
    (`viterbi_decode_batch_fused`) and the rate-switched mixed decode
    every streaming/fleet surface runs (`viterbi_decode_mixed_fused`
    — the stacked 8-rate constant bank, row-selected in-kernel)."""
    return os.environ.get("ZIRIA_FUSED_DEMAP", "0") == "1"


def env_sco_track() -> bool:
    """ZIRIA_RX_SCO_TRACK (default OFF — the flat-profile bit-identity
    contract pins the default DATA decode bitwise): pilot phase-ramp
    tracking for sampling-clock offset."""
    return os.environ.get("ZIRIA_RX_SCO_TRACK", "0") == "1"


# --------------------------------------------------------------- the object


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Every tunable of the compiled transceiver, in one frozen,
    hashable value. Field defaults ARE the tree's historical
    constants; ``None`` decode-mode fields mean "resolve from env"
    (:meth:`resolve`). See the module docstring for how instances
    thread into the jit factories without forking their caches."""

    # streaming window geometry (StreamReceiver / fleet / ServeConfig)
    chunk_len: int = 1 << 13
    frame_len: int = 2048
    max_frames_per_chunk: int = 8         # K
    n_streams: int = 8                    # S, the fleet width
    # power-of-two bucket floors (the pow2_bucket rules)
    sym_bucket_min: int = 4
    capture_bucket_min: int = 512
    bit_bucket_min: int = 128
    # detector parameters (part of _jit_stream_chunk_multi's cache key)
    threshold: float = 0.75
    min_run: int = 33
    dead_zone: int = 320
    # decode-mode knobs; None = fold the env default in resolve()
    viterbi_window: Optional[int] = None
    viterbi_metric: Optional[str] = None
    viterbi_radix: Optional[int] = None
    fused_demap: Optional[bool] = None
    sco_track: Optional[bool] = None

    # -- bucket rules (jaxlint R6: literal floors at call sites are
    # -- findings; these methods are the one place the floors live) --

    def sym_bucket(self, n_sym: int) -> int:
        """Power-of-two symbol bucket — the SHARED TX/RX rule, so a
        loopback's encode and decode geometries agree by
        construction."""
        return pow2_bucket(n_sym, self.sym_bucket_min)

    def capture_bucket(self, n: int) -> int:
        """Power-of-two capture bucket — the ONE padding formula the
        per-capture and batched/streaming acquisition paths share."""
        return pow2_bucket(n, self.capture_bucket_min)

    def bit_bucket(self, n_bits: int) -> int:
        """Power-of-two PSDU bit bucket (the floor keeps tiny frames
        — ACKs, MAC control — in one compile class)."""
        return pow2_bucket(n_bits, self.bit_bucket_min)

    # ------------------------------------------------------- resolution

    def resolve(self) -> "Geometry":
        """Fold the env defaults into every ``None`` decode-mode knob
        — the ONE place CLI/env reaches the geometry (the CLI writes
        scoped env vars; jaxlint R4 keeps every other module out of
        os.environ). Validates metric/radix; idempotent; returns a
        fully-concrete (and therefore cache-key-ready) Geometry."""
        vw = self.viterbi_window
        vm = self.viterbi_metric
        vr = self.viterbi_radix
        if vm is not None and vm not in VITERBI_METRICS:
            raise ValueError(
                f"viterbi_metric {vm!r} is not one of {VITERBI_METRICS}")
        if vr is not None and int(vr) not in VITERBI_RADIXES:
            raise ValueError(
                f"viterbi_radix {vr!r} is not one of {VITERBI_RADIXES}")
        return dataclasses.replace(
            self,
            viterbi_window=env_viterbi_window() if vw is None else int(vw),
            viterbi_metric=env_viterbi_metric() if vm is None else vm,
            viterbi_radix=env_viterbi_radix() if vr is None else int(vr),
            fused_demap=(env_fused_demap() if self.fused_demap is None
                         else bool(self.fused_demap)),
            sco_track=(env_sco_track() if self.sco_track is None
                       else bool(self.sco_track)))

    # ---------------------------------------------------- serialization

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.as_dict(), sort_keys=True)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Geometry":
        """Strict inverse of :meth:`as_dict`: unknown keys raise (a
        record from a future field set must not silently drop a
        field)."""
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            raise ValueError(
                f"unknown Geometry field(s): {', '.join(unknown)}")
        return cls(**d)

    @classmethod
    def from_json(cls, s: str) -> "Geometry":
        return cls.from_dict(json.loads(s))


#: the shared default instance — ctor defaults across framebatch /
#: serve / link derive from this, so "1 << 13" exists ONCE (above)
DEFAULT = Geometry()
