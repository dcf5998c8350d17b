"""Uplink compression methods as thin shells over the stateless codecs.

A *method* (``make_method``) is host-side configuration only: it knows how
to build one :class:`repro.core.codecs.Codec` per parameter group
(``build_codec``).  All array state -- per-client bases, error memories,
rSVD key chains, the SVDFed shared basis -- lives in explicit codec state
pytrees owned by the round engines, so the same codec runs vmapped over
the client axis inside the fused single-XLA-program round *and* per client
in the reference loop.  (The old ``*Method`` classes kept that state in
Python dicts keyed by ``(client, path)``, which is why only GradESTC could
run fused before.)

:class:`RoundAccountant` is the host half of the protocol, shared by both
engines: it consumes the packed int32 stats vector a round produces (one
row of the K-round stats block the scan engine fetches per chunk) and
charges the ledger in exact integer-bit arithmetic.  There is no host-side
per-round codec config left to advance -- GradESTC's Formula 13 candidate
count is traced shared state updated in-jit, and the ``d`` a round used
travels in its stats row.  Byte parity between the engines is by
construction -- there is exactly one charging code path.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.codecs import (
    Codec, EFCodec, FedPAQCodec, FedQClipCodec, GradESTCCodec,
    SERVER_CLIENT_ID, SignSGDCodec, SVDFedCodec, TopKCodec,
    client_layer_keys, round_base_key,
)
from repro.core.policy import CompressionPolicy, LayerPlan

__all__ = [
    "make_method", "client_layer_keys", "round_base_key", "path_index",
    "build_codecs", "build_downlink_codecs", "pack_round_stats",
    "RoundAccountant",
    "FedAvgMethod", "TopKMethod", "FedPAQMethod", "SignSGDMethod",
    "FedQClipMethod", "SVDFedMethod", "GradESTCMethod",
]


def path_index(policy: CompressionPolicy) -> Dict[str, int]:
    """Stable group-name -> int map (sorted order) for PRNG key derivation."""
    return {name: i for i, name in enumerate(sorted(policy.plans))}


class _MethodShell:
    """Host-side method config.  ``build_codec`` returns the codec for one
    parameter group, or ``None`` when that group ships raw."""

    name = "?"

    def __init__(self, seed: int = 0, **_):
        self.seed = seed

    def build_codec(self, path: str, plan: LayerPlan, path_idx: int,
                    use_pallas: bool = False,
                    pallas_interpret: Optional[bool] = None) -> Optional[Codec]:
        raise NotImplementedError


class FedAvgMethod(_MethodShell):
    """Uncompressed reference: every group ships raw."""

    name = "fedavg"

    def build_codec(self, path, plan, path_idx, use_pallas=False,
                    pallas_interpret=None):
        return None


class TopKMethod(_MethodShell):
    """Per-tensor magnitude top-k with error memory (ref [23])."""

    name = "topk"

    def __init__(self, frac: float = 0.1, **kw):
        super().__init__(**kw)
        self.frac = frac

    def build_codec(self, path, plan, path_idx, use_pallas=False,
                    pallas_interpret=None):
        return TopKCodec(plan.raw_scalars, frac=self.frac, path_idx=path_idx)


class FedPAQMethod(_MethodShell):
    """Stochastic uniform quantization of every tensor (ref [21])."""

    name = "fedpaq"

    def __init__(self, bits: int = 8, **kw):
        super().__init__(**kw)
        self.bits = bits

    def build_codec(self, path, plan, path_idx, use_pallas=False,
                    pallas_interpret=None):
        return FedPAQCodec(plan.raw_scalars, bits=self.bits, path_idx=path_idx,
                           use_pallas=use_pallas,
                           pallas_interpret=pallas_interpret)


class SignSGDMethod(_MethodShell):
    name = "signsgd"

    def build_codec(self, path, plan, path_idx, use_pallas=False,
                    pallas_interpret=None):
        return SignSGDCodec(plan.raw_scalars, path_idx=path_idx,
                            use_pallas=use_pallas,
                            pallas_interpret=pallas_interpret)


class FedQClipMethod(_MethodShell):
    """Clipped + quantized updates (ref [42])."""

    name = "fedqclip"

    def __init__(self, clip: float = 100.0, bits: int = 8, **kw):
        super().__init__(**kw)
        self.clip = clip
        self.bits = bits

    def build_codec(self, path, plan, path_idx, use_pallas=False,
                    pallas_interpret=None):
        return FedQClipCodec(plan.raw_scalars, clip=self.clip, bits=self.bits,
                             path_idx=path_idx, use_pallas=use_pallas,
                             pallas_interpret=pallas_interpret)


class SVDFedMethod(_MethodShell):
    """Shared server-fit basis, coefficient uplink between refits (ref [12])."""

    name = "svdfed"

    def __init__(self, policy: CompressionPolicy, gamma: float = 8.0,
                 wire_dtype: str = "f32", **kw):
        super().__init__(**kw)
        self.policy = policy
        self.gamma = gamma
        # explicit (not **kw): _MethodShell swallows unknown kwargs, and a
        # silently dropped wire_dtype would charge f32 bits for an f32 wire
        # the caller believed was int8.
        self.wire_dtype = wire_dtype

    def build_codec(self, path, plan, path_idx, use_pallas=False,
                    pallas_interpret=None):
        if not plan.compress:
            return None
        return SVDFedCodec(plan, gamma=self.gamma, seed=self.seed,
                           path_idx=path_idx, use_pallas=use_pallas,
                           pallas_interpret=pallas_interpret,
                           wire_dtype=self.wire_dtype)


class GradESTCMethod(_MethodShell):
    """The paper's method.  variant in {"full", "first", "all", "k"}
    (Table IV ablations); ``ef`` enables error feedback (beyond-paper)."""

    name = "gradestc"

    def __init__(self, policy: CompressionPolicy, variant: str = "full",
                 alpha: float = 1.3, beta: float = 1.0, ef: bool = False,
                 wire_dtype: str = "f32", **kw):
        assert variant in ("full", "first", "all", "k")
        super().__init__(**kw)
        self.policy = policy
        self.variant = variant
        self.alpha, self.beta = alpha, beta
        self.ef = ef
        # explicit (not **kw) for the same reason as SVDFedMethod
        self.wire_dtype = wire_dtype

    def build_codec(self, path, plan, path_idx, use_pallas=False,
                    pallas_interpret=None):
        if not plan.compress:
            return None
        codec = GradESTCCodec(plan, seed=self.seed, path_idx=path_idx,
                              variant=self.variant, alpha=self.alpha,
                              beta=self.beta, use_pallas=use_pallas,
                              pallas_interpret=pallas_interpret,
                              wire_dtype=self.wire_dtype)
        if self.ef:
            codec = EFCodec(codec, (plan.stack, plan.l, plan.m))
        return codec


def make_method(name: str, policy: Optional[CompressionPolicy] = None, **kw):
    name = name.lower()
    if name == "fedavg":
        return FedAvgMethod(**kw)
    if name == "topk":
        return TopKMethod(**kw)
    if name == "fedpaq":
        return FedPAQMethod(**kw)
    if name == "signsgd":
        return SignSGDMethod(**kw)
    if name == "fedqclip":
        return FedQClipMethod(**kw)
    if name == "svdfed":
        assert policy is not None
        return SVDFedMethod(policy, **kw)
    if name.startswith("gradestc"):
        assert policy is not None
        variant = "full"
        ef = False
        if "-" in name:
            suffix = name.split("-", 1)[1]
            if suffix == "ef":
                ef = True
            else:
                variant = suffix
        return GradESTCMethod(policy, variant=variant, ef=ef, **kw)
    raise ValueError(f"unknown method {name!r}")


def build_codecs(method, policy: CompressionPolicy, group_paths,
                 use_pallas: bool = False,
                 pallas_interpret: Optional[bool] = None) -> Dict[str, Codec]:
    """One codec per compressed group; paths absent from the result ship raw."""
    pidx = path_index(policy)
    out: Dict[str, Codec] = {}
    for path in group_paths:
        codec = method.build_codec(path, policy.plans[path], pidx[path],
                                   use_pallas, pallas_interpret)
        if codec is not None:
            out[path] = codec
    return out


def build_downlink_codecs(policy: CompressionPolicy, group_paths, seed: int,
                          use_pallas: bool = False,
                          pallas_interpret: Optional[bool] = None,
                          ) -> Dict[str, Codec]:
    """The shared server-side GradESTC codec compressing the broadcast
    (``FLConfig.downlink_compress``); one 'client' with id
    ``SERVER_CLIENT_ID``, seeded independently of the uplink codecs."""
    method = make_method("gradestc", policy=policy, seed=seed + 101)
    return build_codecs(method, policy, group_paths, use_pallas,
                        pallas_interpret)


def pack_round_stats(reds: Dict[str, jnp.ndarray],
                     dl_reds: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """The round's packed stats vector: reduced int32 stats per sorted
    uplink path, then per sorted downlink path.  Both engines build the
    transfer through this one function so the layout
    ``RoundAccountant.consume`` unpacks cannot drift between them.
    Stats-free rounds still ship a one-element placeholder -- the single
    measured host sync stays uniform across methods."""
    parts = ([reds[p] for p in sorted(reds)]
             + [dl_reds[p] for p in sorted(dl_reds)])
    if parts and sum(int(p.size) for p in parts):
        return jnp.concatenate(parts)
    return jnp.zeros((1,), jnp.int32)


class RoundAccountant:
    """Host half of the codec protocol, shared verbatim by both engines.

    Consumes one round's packed int32 stats row (rows of the single
    measured per-chunk ``host_fetch`` in the scan engine; one fetch per
    round in the reference loop), charges uplink/downlink in exact integer
    bits (``CommLedger.charge_uplink_bits``), and merges host metrics
    (``sum_d``).  Pure per-row: it carries no per-round state, so rows may
    be consumed late (the engine defers a chunk's fetch one chunk) as long
    as ``round_idx`` pins each charge to its slot.
    """

    def __init__(self, codecs: Dict[str, Codec], dl_codecs: Dict[str, Codec],
                 policy: CompressionPolicy, group_paths, n_sel: int,
                 downlink_enabled: bool = False):
        self.codecs = {p: codecs[p] for p in sorted(codecs)}
        self.dl_codecs = {p: dl_codecs[p] for p in sorted(dl_codecs)}
        self.n_sel = n_sel
        self.downlink_enabled = downlink_enabled
        self.metrics: Dict[str, int] = {}
        self.raw_scalars_per_client = sum(
            policy.plans[p].raw_scalars for p in group_paths if p not in codecs
        )
        self.model_scalars = sum(
            policy.plans[p].raw_scalars for p in group_paths
        )
        self.dl_raw_scalars = sum(
            policy.plans[p].raw_scalars for p in group_paths
            if p not in dl_codecs
        )
        self.packed_len = (sum(c.stats_len for c in self.codecs.values())
                           + sum(c.stats_len for c in self.dl_codecs.values()))
        #: round -> {uplink path: its reduced stats}, what each charge was
        #: computed from (exported as ``FLResult.extra["uplink_stats"]``)
        self.uplink_stats: Dict[int, Dict[str, Tuple[int, ...]]] = {}

    def consume(self, packed: np.ndarray, ledger, rnd: int) -> None:
        """Charge the ledger for round ``rnd`` from its fetched stats row."""
        packed = np.asarray(packed).reshape(-1)
        expected = max(self.packed_len, 1)    # pack_round_stats placeholder
        if packed.size != expected:
            raise ValueError(
                f"packed stats layout drift: got {packed.size} entries, "
                f"expected {expected} -- engine packing disagrees with the "
                f"registered codecs")
        off = 0
        bits = 32 * self.raw_scalars_per_client * self.n_sel
        stats = self.uplink_stats[rnd] = {}
        for path, codec in self.codecs.items():
            red = packed[off: off + codec.stats_len]
            off += codec.stats_len
            stats[path] = tuple(int(x) for x in red)
            bits += codec.charge_bits(red, self.n_sel)
            for k, v in codec.host_metrics(red, self.n_sel).items():
                self.metrics[k] = self.metrics.get(k, 0) + v
        # round_idx pins the charge to round ``rnd``'s ledger slot: the
        # chunked engine has usually begun the next chunk by the time round
        # rnd's stats arrive.
        ledger.charge_uplink_bits(bits, group=f"round{rnd}", round_idx=rnd)

        if self.downlink_enabled:
            dbits = 32 * self.dl_raw_scalars
            for path, codec in self.dl_codecs.items():
                red = packed[off: off + codec.stats_len]
                off += codec.stats_len
                dbits += codec.charge_bits(red, 1)
            ledger.charge_downlink_bits(dbits * self.n_sel)
        else:
            ledger.charge_downlink_bits(32 * self.model_scalars * self.n_sel)
