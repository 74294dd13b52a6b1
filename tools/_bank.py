"""Scratch-dir resume bank shared by the TPU harvest tools.

Chip time comes in short windows; each tool banks every finished unit of work
(a calibration pipeline, a sweep point) so a re-entering run spends
the next window only on what is missing. One implementation so the
aging rules cannot diverge between tools (review r5): every entry
carries its OWN capture time ``_t`` and ages out individually —
re-banking a new entry must not revive old ones.
"""

from __future__ import annotations

import contextlib
import json
import os
import time

try:
    import fcntl
except ImportError:                   # pragma: no cover - non-POSIX
    fcntl = None

SCRATCH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "..", ".bench_scratch")
MAX_AGE_S = 6 * 3600.0


def _path(name: str) -> str:
    return os.path.join(SCRATCH, name + ".json")


@contextlib.contextmanager
def _bank_lock(name: str):
    """Serialize the read-modify-write of one bank file across
    concurrent bankers (ADVICE r5 #4: two tools banking at once could
    lose each other's entries — previously mitigated only by the
    /tmp/tpu_busy serialization convention). An flock on a sidecar
    .lock file: advisory, crash-safe (the OS releases with the fd),
    so no stale-lock aging is needed."""
    if fcntl is None:                 # pragma: no cover - non-POSIX
        yield
        return
    fd = os.open(_path(name) + ".lock",
                 os.O_CREAT | os.O_WRONLY, 0o644)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        yield
    finally:
        fcntl.flock(fd, fcntl.LOCK_UN)
        os.close(fd)


def load_bank(name: str, platform: str, match: dict = None,
              max_age_s: float = MAX_AGE_S, now: float = None) -> dict:
    """key -> entry for this platform (and ``match`` file-level fields,
    e.g. a trellis length), dropping entries older than ``max_age_s``
    by their individual capture times."""
    try:
        with open(_path(name)) as f:
            saved = json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}
    if saved.get("platform") != platform:
        return {}
    for k, v in (match or {}).items():
        if saved.get(k) != v:
            return {}
    now = time.time() if now is None else now
    return {k: e for k, e in saved.get("entries", {}).items()
            if isinstance(e, dict) and now - e.get("_t", 0) < max_age_s}


def save_entry(name: str, platform: str, key: str, entry: dict,
               match: dict = None) -> None:
    """Bank one finished unit (stamped with its capture time),
    atomically. A platform/match mismatch discards the old bank.
    The whole read-modify-write runs under the bank's lock file so
    concurrent bankers serialize instead of losing entries."""
    os.makedirs(SCRATCH, exist_ok=True)
    with _bank_lock(name):
        try:
            with open(_path(name)) as f:
                saved = json.load(f)
        except (OSError, json.JSONDecodeError):
            saved = {}
        if saved.get("platform") != platform or any(
                saved.get(k) != v for k, v in (match or {}).items()):
            saved = {}
        saved["platform"] = platform
        saved.update(match or {})
        saved.setdefault("entries", {})[key] = {**entry,
                                                "_t": time.time()}
        tmp = _path(name) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(saved, f)
        os.replace(tmp, _path(name))


def strip(entry: dict) -> dict:
    """An entry's payload without the bank's bookkeeping."""
    return {k: v for k, v in entry.items() if k != "_t"}
