"""802.11a/g OFDM PHY rate parameters.

Counterpart of the per-rate dispatch tables inside the reference's
`modulating.blk`/`encoding.blk`/`parsePLCPHeader` (SURVEY.md §2.3).
Values are the standard's Table 78 (§17.3.2.2) from standard knowledge.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class RateParams:
    mbps: int
    n_bpsc: int        # coded bits per subcarrier
    n_cbps: int        # coded bits per OFDM symbol
    n_dbps: int        # data bits per OFDM symbol
    coding: str        # "1/2" | "2/3" | "3/4"
    signal_bits: int   # 4-bit RATE field, R1 (transmitted first) = MSB here


RATES: Dict[int, RateParams] = {
    6:  RateParams(6,  1, 48,  24,  "1/2", 0b1101),
    9:  RateParams(9,  1, 48,  36,  "3/4", 0b1111),
    12: RateParams(12, 2, 96,  48,  "1/2", 0b0101),
    18: RateParams(18, 2, 96,  72,  "3/4", 0b0111),
    24: RateParams(24, 4, 192, 96,  "1/2", 0b1001),
    36: RateParams(36, 4, 192, 144, "3/4", 0b1011),
    48: RateParams(48, 6, 288, 192, "2/3", 0b0001),
    54: RateParams(54, 6, 288, 216, "3/4", 0b0011),
}

SIGNAL_BITS_TO_MBPS = {p.signal_bits: m for m, p in RATES.items()}

# the ONE rate ordering every mixed-rate ``lax.switch`` uses (TX
# encode_many and RX decode_data_mixed build their branch lists from
# it; a disagreement would decode a lane at the wrong rate) — pinned
# by tests/test_rx_mixed_dispatch.py::test_rate_index_order...
RATE_MBPS_ORDER = tuple(sorted(RATES))
RATE_INDEX = {m: i for i, m in enumerate(RATE_MBPS_ORDER)}
MAX_DBPS = max(p.n_dbps for p in RATES.values())     # 216 (54 Mbps)

N_SERVICE_BITS = 16
N_TAIL_BITS = 6

# SIGNAL's LENGTH field has 12 bits, so no PSDU exceeds 4095 bytes and
# no DATA field 32 782 bits: 152 symbols at 54 Mbit/s, and at most
# 152 * 216 = 32 832 trellis steps at ANY rate (whole symbols: 152 x
# 216, 171 x 192, 228 x 144, 342 x 96, 456 x 72; 683 x 48 and 911 x 36
# fall just under)
LENGTH_FIELD_BITS = 12
MAX_PSDU_BYTES = (1 << LENGTH_FIELD_BITS) - 1
MAX_DATA_BITS = N_SERVICE_BITS + 8 * MAX_PSDU_BYTES + N_TAIL_BITS
MAX_SYM_AT_MAX_DBPS = -(-MAX_DATA_BITS // MAX_DBPS)           # 152


def n_symbols(length_bytes: int, rate: RateParams) -> int:
    """Number of DATA OFDM symbols for a PSDU of `length_bytes`."""
    n_bits = N_SERVICE_BITS + 8 * length_bytes + N_TAIL_BITS
    return -(-n_bits // rate.n_dbps)


def mixed_trellis_steps(n_sym_bucket: int) -> int:
    """Trellis length of the rate-agnostic mixed decode at a symbol
    bucket: the bucket at 54 Mbit/s, bound by the longest DATA field
    the LENGTH field can announce. The identity (``n_sym_bucket *
    MAX_DBPS``) for every bucket of 152 symbols or fewer; 32 832 steps,
    not 221 184, at the served bucket of 1024."""
    return min(n_sym_bucket, MAX_SYM_AT_MAX_DBPS) * MAX_DBPS


def mixed_branch_symbols(n_sym_bucket: int, rate: RateParams) -> int:
    """DATA symbols the mixed decode's branch for `rate` demaps: the
    bucket, or the fewer that already hold `mixed_trellis_steps` rows
    at this rate (1024, 912, 684, 456, 342, 228, 171, 152 of a
    1024-symbol bucket for 6 ... 54 Mbit/s)."""
    return min(n_sym_bucket,
               -(-mixed_trellis_steps(n_sym_bucket) // rate.n_dbps))
