"""802.11 data scrambler / descrambler.

Counterpart of the reference's `scramble.blk` / descrambler (SURVEY.md
§2.3). The scrambler is the 7-bit LFSR x^7 + x^4 + 1 whose output
sequence is XORed onto the data bits (additive scrambling), seeded per
frame; the same primitive with an all-ones seed generates the 127-bit
pilot-polarity sequence.

TPU-native design: x^7+x^4+1 is primitive, so every nonzero seed
generates the same maximal-length 127-bit sequence at some phase. The
LFSR is linear over GF(2), so one period is the XOR of the seven
unit-seed periods (a constant, built at import) that the seed's bits
select — no dependent step; we *tile* the period over the frame and
XOR — one fused elementwise op over the whole bit stream instead of a
per-bit sequential loop. Seed recovery for the descrambler
is a 128-row precomputed table match (the SERVICE field's first 7 bits
are zero, so the received first 7 bits expose the sequence phase) —
AutoLUT-style precomputation (SURVEY.md §2.1).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ziria_tpu.utils.bits import uint_to_bits, xor_reduce


def np_lfsr_sequence_127(seed_bits: np.ndarray) -> np.ndarray:
    """Host-side (numpy) version of the 127-bit sequence, for module-load
    constants (pilot polarity, precomputed scrambling tables) — avoids a
    JAX dispatch at import time."""
    s = list(np.asarray(seed_bits, np.uint8))
    out = []
    for _ in range(127):
        fb = s[6] ^ s[3]
        out.append(fb)
        s = [fb] + s[:6]
    return np.array(out, np.uint8)


#: the period each single seed bit generates, (7, 127): by linearity
#: a seed's period is the XOR of the rows its bits select. Not a
#: 128-row table indexed by the seed: that gather read row 0 on the
#: TPU inside `tx.encode_many_graph`'s vmapped switch (PERF.md, PR 27).
_UNIT_PERIODS = np.stack([np_lfsr_sequence_127(row)
                          for row in np.eye(7, dtype=np.uint8)])


def lfsr_sequence_127(seed_bits) -> jnp.ndarray:
    """One period (127 bits) of the scrambler sequence from a 7-bit seed.

    seed_bits: (7,) uint8, seed_bits[k] = x_{k+1} of the standard's
    initial state (seed_bits[6] is x7). Output bit t is
    x7(t) XOR x4(t); state shifts with that bit fed back into x1.
    """
    seed_bits = jnp.asarray(seed_bits, jnp.uint8)
    return xor_reduce(seed_bits[:, None] & jnp.asarray(_UNIT_PERIODS), (0,))


def scramble_bits(bits, seed_bits) -> jnp.ndarray:
    """XOR the data bits with the scrambler sequence (additive)."""
    bits = jnp.asarray(bits, jnp.uint8)
    n = bits.shape[0]
    period = lfsr_sequence_127(seed_bits)
    reps = -(-n // 127)
    seq = jnp.tile(period, reps)[:n]
    return bits ^ seq


# descrambling is the same XOR
descramble_bits = scramble_bits


def _seed_table() -> np.ndarray:
    """first 7 sequence bits for every 7-bit seed (numpy at import)."""
    tab = np.zeros((128, 7), np.uint8)
    for seed in range(128):
        s = [(seed >> k) & 1 for k in range(7)]
        out = []
        for _ in range(7):
            fb = s[6] ^ s[3]
            out.append(fb)
            s = [fb] + s[:6]
        tab[seed] = out
    return tab


_SEED_TABLE = _seed_table()


def recover_seed(first7_bits) -> jnp.ndarray:
    """Recover the scrambler seed from the first 7 received (descrambler
    input) bits, which equal the sequence bits because the SERVICE field
    starts with zeros. Returns (7,) uint8 seed bits."""
    first7 = jnp.asarray(first7_bits, jnp.uint8)
    tab = jnp.asarray(_SEED_TABLE)
    match = jnp.all(tab == first7[None, :], axis=1)
    seed = jnp.argmax(match).astype(jnp.uint32)
    return uint_to_bits(seed, 7)


def np_scramble_ref(bits: np.ndarray, seed_bits: np.ndarray) -> np.ndarray:
    """Independent oracle: per-bit LFSR loop. Tests only."""
    s = list(np.asarray(seed_bits, np.uint8))
    out = []
    for b in np.asarray(bits, np.uint8):
        fb = s[6] ^ s[3]
        out.append(b ^ fb)
        s = [fb] + s[:6]
    return np.array(out, np.uint8)
