#!/bin/bash
# "committed => executed" gate (VERDICT r3 weak #1 / next #2): refuse to
# commit a staged test file that has not been run. Runs pytest on every
# staged tests/test_*.py; skips cleanly when none are staged. Install:
#   ln -sf ../../tools/precommit.sh .git/hooks/pre-commit
# Escape hatch for WIP commits: ZIRIA_SKIP_TESTGATE=1 git commit ...
set -u
cd "$(git rev-parse --show-toplevel)"
[ "${ZIRIA_SKIP_TESTGATE:-0}" = "1" ] && exit 0

# jaxlint gate (ISSUE 8/9): pure AST, no jax import, sub-5s — a
# cache-key/hygiene finding must not reach a commit
if ! python -m ziria_tpu lint ziria_tpu/; then
  echo "[precommit] jaxlint found issues — commit refused" >&2
  echo "[precommit] (ZIRIA_SKIP_TESTGATE=1 to override for WIP)" >&2
  exit 1
fi

# chaos smoke (ISSUE 12): the fault-injection + guarded-dispatch
# machinery exercised against stub dispatches — sub-10s, CPU-only,
# never imports jax (works through TPU probe hangs, like the lint
# gate). A broken resilience layer must not reach a commit.
if ! timeout 30 python tools/chaos_smoke.py; then
  echo "[precommit] chaos smoke FAILED (tools/chaos_smoke.py) —" \
       "commit refused" >&2
  echo "[precommit] (ZIRIA_SKIP_TESTGATE=1 to override for WIP)" >&2
  exit 1
fi

# serve smoke (ISSUE 13): the continuous-batching server's
# admission/backpressure/shed/evict/drain state machine exercised
# against a stub receiver — sub-second, never imports jax (works
# through TPU probe hangs, like chaos_smoke and the lint gate).
if ! timeout 30 python tools/serve_smoke.py; then
  echo "[precommit] serve smoke FAILED (tools/serve_smoke.py) —" \
       "commit refused" >&2
  echo "[precommit] (ZIRIA_SKIP_TESTGATE=1 to override for WIP)" >&2
  exit 1
fi

# durability smoke (ISSUE 14): the crash-safe journal / snapshot /
# recovery machinery exercised against a stub receiver — sub-second,
# never imports jax (works through TPU probe hangs, like its
# siblings). A broken durability layer must not reach a commit.
if ! timeout 30 python tools/durability_smoke.py; then
  echo "[precommit] durability smoke FAILED" \
       "(tools/durability_smoke.py) — commit refused" >&2
  echo "[precommit] (ZIRIA_SKIP_TESTGATE=1 to override for WIP)" >&2
  exit 1
fi

# geometry smoke (ISSUE 16): the declarative Geometry object's
# construct/resolve/serialize round trip plus the pinned
# default constants — sub-second, never imports jax (works through
# TPU probe hangs, like its siblings). A drifted default would break
# the no-op-by-construction guarantee behind every compiled surface.
if ! timeout 30 python tools/geometry_smoke.py; then
  echo "[precommit] geometry smoke FAILED (tools/geometry_smoke.py)" \
       "— commit refused" >&2
  echo "[precommit] (ZIRIA_SKIP_TESTGATE=1 to override for WIP)" >&2
  exit 1
fi

mapfile -t staged < <(git diff --cached --name-only --diff-filter=ACM |
                      grep -E '^tests/test_.*\.py$' || true)
[ ${#staged[@]} -eq 0 ] && exit 0
# pytest runs the WORKTREE copy; that only certifies the INDEX content
# when the two are identical — refuse a partially-staged test file
for f in "${staged[@]}"; do
  if ! git diff --quiet -- "$f"; then
    echo "[precommit] $f differs between index and worktree;" >&2
    echo "[precommit] re-add it (or stash the WIP) so the gate runs" \
         "what will be committed" >&2
    exit 1
  fi
done
echo "[precommit] running staged test files: ${staged[*]}" >&2
if ! timeout 1200 python -m pytest "${staged[@]}" -q -x; then
  echo "[precommit] staged tests FAILED — commit refused" >&2
  echo "[precommit] (ZIRIA_SKIP_TESTGATE=1 to override for WIP)" >&2
  exit 1
fi
exit 0
