"""Communication accounting -- exact uplink/downlink byte bookkeeping.

The paper's headline numbers (Table III) are uplink GB at a target accuracy
and total uplink GB.  This module provides a tiny ledger used by the FL
runtime and the benchmarks so every method is charged identically:

  * totals accumulate as **exact integer bits** (``charge_uplink_bits`` /
    ``charge_downlink_bits`` -- the codecs' ``charge_bits`` contract), so
    no float rounding can skew Table III totals at any scale; sub-word
    codes (quantization, signs) are integral in bits even when fractional
    in scalars.  The byte-valued views (``uplink_total`` & co.) divide by 8
    on read -- dyadic rationals, exact in f64;
  * per-round and per-group resolution;
  * uplink  = client -> server (gradient direction);
    downlink = server -> client (model broadcast), counted once per round as
    the full model unless downlink compression is enabled.

It also holds the runtime's two measurement hooks: the device->host fetch
counter (:func:`host_fetch`) and :func:`span`, the named host interval that
the FL engines mark their phases with (DESIGN.md "Tracing").
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["CommLedger", "bytes_h", "host_fetch", "host_sync_count",
           "reset_host_sync_count", "span"]


def span(name: str, **counts) -> TraceAnnotation:
    """A named host interval with integer arguments, recorded in the
    profiler's trace when one is being taken (``jax.profiler.trace``) and
    about a microsecond of host time when none is.  Use it as a context
    manager; an argument known only at the end goes in through
    ``set_metadata`` before the block exits."""
    return TraceAnnotation(name, **counts)


#: Device->host transfer counter.  Every blocking fetch in the FL runtime is
#: routed through :func:`host_fetch` so benchmarks can *measure* the per-round
#: host-sync count instead of asserting it by inspection (DESIGN.md Sec. 8:
#: the fused round engine's contract is exactly one fetch per round).
_HOST_SYNCS = 0


def host_fetch(x):
    """Materialize a device value on the host, counting the sync (span
    ``fl.host_fetch``, argument ``bytes``)."""
    global _HOST_SYNCS
    _HOST_SYNCS += 1
    with span("fl.host_fetch", bytes=int(getattr(x, "nbytes", 0))):
        return np.asarray(x)


def host_sync_count() -> int:
    return _HOST_SYNCS


def reset_host_sync_count() -> None:
    global _HOST_SYNCS
    _HOST_SYNCS = 0


def bytes_h(b: float) -> str:
    """Human-readable bytes."""
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(b) < 1024.0 or unit == "TB":
            return f"{b:.3f} {unit}"
        b /= 1024.0
    return f"{b:.3f} TB"


@dataclass
class CommLedger:
    uplink_bits: int = 0
    downlink_bits: int = 0
    per_round_uplink_bits: List[int] = field(default_factory=list)
    per_group_bits: Dict[str, int] = field(default_factory=dict)

    def begin_round(self) -> None:
        self.per_round_uplink_bits.append(0)

    def charge_uplink_bits(self, bits: int, group: str = "_",
                           round_idx: int | None = None) -> None:
        """Charge exact integer ``bits`` of uplink.  ``round_idx`` pins the
        charge to an explicit round slot -- required by the chunked fused
        engine, which consumes a whole K-round stats block after round
        ``start+K-1`` has begun (so "the last slot" is not round r's)."""
        bits = int(bits)
        self.uplink_bits += bits
        if round_idx is not None:
            if not 0 <= round_idx < len(self.per_round_uplink_bits):
                raise IndexError(
                    f"charge_uplink round_idx={round_idx} but only "
                    f"{len(self.per_round_uplink_bits)} rounds begun")
            self.per_round_uplink_bits[round_idx] += bits
        elif self.per_round_uplink_bits:
            self.per_round_uplink_bits[-1] += bits
        self.per_group_bits[group] = self.per_group_bits.get(group, 0) + bits

    def charge_downlink_bits(self, bits: int) -> None:
        self.downlink_bits += int(bits)

    # -- byte-valued views (exact: bits are integers, /8 is dyadic) --------
    @property
    def uplink_total(self) -> float:
        return self.uplink_bits / 8

    @property
    def downlink_total(self) -> float:
        return self.downlink_bits / 8

    @property
    def per_round_uplink(self) -> List[float]:
        return [b / 8 for b in self.per_round_uplink_bits]

    @property
    def per_group(self) -> Dict[str, float]:
        return {g: b / 8 for g, b in self.per_group_bits.items()}

    @property
    def rounds(self) -> int:
        return len(self.per_round_uplink_bits)

    def uplink_at(self, round_idx: int) -> float:
        """Cumulative uplink bytes through round ``round_idx`` (inclusive)."""
        return sum(self.per_round_uplink_bits[: round_idx + 1]) / 8

    def summary(self) -> str:
        lines = [
            f"uplink total   : {bytes_h(self.uplink_total)}",
            f"downlink total : {bytes_h(self.downlink_total)}",
            f"rounds         : {self.rounds}",
        ]
        if self.per_group:
            lines.append("per-group uplink:")
            for g, b in sorted(self.per_group.items(), key=lambda kv: -kv[1]):
                lines.append(f"  {g:40s} {bytes_h(b)}")
        return "\n".join(lines)
