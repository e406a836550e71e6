"""Operations and bytes that one CIM layer call needs, whatever
implements it (see PERF.md, "Roofline count").

A call multiplies M activation rows of K integer codes by a (K, N) weight
of ``weight_bits`` stored on ``S = weight_bits / cell_bits`` bit-split
cells. Every bit split of every array tile goes through the ADC on its
own, so no implementation can merge the splits: ops = 2*M*K*N*S. Bytes
are the packed weight, the activation codes read once, the output in the
compute dtype and the per-column ``s_p`` and dequant scales of every
(split, array tile, column).
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class CimCall:
    m: int                 # activation rows (tokens, or B*H'*W' for a conv)
    k: int                 # contraction length (kh*kw*C_in for a conv)
    n: int                 # output columns
    act_elems: int         # activation codes read (B*H*W*C_in for a conv)
    k_tiles: int           # CIM array tiles along K
    out_bytes: int         # bytes of one output element
    count: int = 1         # calls of this shape per unit of work

    def ops(self, cim: dict) -> float:
        s = splits(cim)
        return 2.0 * self.m * self.k * self.n * s * self.count

    def bytes(self, cim: dict) -> float:
        s = splits(cim)
        w = self.k * self.n * cim["weight_bits"] / 8
        a = self.act_elems * cim["act_bits"] / 8
        o = self.m * self.n * self.out_bytes
        scales = 2 * s * self.k_tiles * self.n * 4
        return float(w + a + o + scales) * self.count

    def least_seconds(self, cim: dict, peaks: dict) -> tuple:
        """(seconds, bound): the larger of ops over the int8 peak and bytes
        over the HBM bandwidth, and which of the two it is."""
        t_ops = self.ops(cim) / peaks["int8_ops"]
        t_bytes = self.bytes(cim) / peaks["hbm_bytes_s"]
        return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")


def splits(cim: dict) -> int:
    return math.ceil(cim["weight_bits"] / cim["cell_bits"])


def conv_call(b: int, h: int, w: int, c_in: int, c_out: int, kh: int,
              kw: int, stride: int, cim: dict, out_bytes: int) -> CimCall:
    """SAME padding. Tiles hold ``floor(rows / (kh*kw))`` whole input
    channels with all their taps (the paper's stretched-kernel rule)."""
    ho, wo = math.ceil(h / stride), math.ceil(w / stride)
    c_per_array = max(1, cim["array_rows"] // (kh * kw))
    return CimCall(m=b * ho * wo, k=kh * kw * c_in, n=c_out,
                   act_elems=b * h * w * c_in,
                   k_tiles=math.ceil(c_in / c_per_array),
                   out_bytes=out_bytes)


def least_time(calls, cim: dict, peaks: dict) -> dict:
    """Sum of per-call least times, with the seconds each bound holds."""
    out = {"seconds": 0.0, "ops": 0.0, "bytes": 0.0,
           "ops_bound_s": 0.0, "bytes_bound_s": 0.0}
    for c in calls:
        t, bound = c.least_seconds(cim, peaks)
        out["seconds"] += t
        out[f"{bound}_bound_s"] += t
        out["ops"] += c.ops(cim)
        out["bytes"] += c.bytes(cim)
    return out
