"""SortSpec: the single configuration object of the `repro.sort` front-door.

One spec consolidates what used to be spread over `HSSConfig`,
`ExchangeConfig` and per-algorithm driver kwargs (`hss_sort`, `sample_sort`,
`ams_sort`, `two_stage_sort` each had their own). The spec is a frozen
dataclass so it can be shared, logged, and swept in benchmarks; `repro.sort`
translates it into the legacy config objects at the core boundary.

    from repro.sort import SortSpec, sort
    out = sort(x, SortSpec(algorithm="hss", eps=0.05, exchange="allgather"))
"""
from __future__ import annotations

import dataclasses
from typing import Any

from repro.core.common import HSSConfig
from repro.core.exchange import ExchangeConfig

ALGORITHMS = ("hss", "sample_random", "sample_regular", "ams", "multistage")

ON_OVERFLOW = ("raise", "retry", "spill")

VERIFY = ("off", "cheap", "full")

ON_VERIFY_FAILURE = ("raise", "retry", "fallback")


@dataclasses.dataclass(frozen=True)
class SortSpec:
    """Everything the unified `sort()`/`argsort()`/`sort_kv()` surface needs.

    Algorithm selection:
      algorithm      one of ALGORITHMS (see repro.sort.partitioners registry).
      eps            load-balance slack: each output shard <= (1+eps) N/p keys.

    Splitter determination (HSS + multistage; see HSSConfig):
      rounds, sample_per_shard, adaptive — forwarded to HSSConfig.
      total_sample   sample_random / ams: overall sample-size override.
      s              sample_regular (PSRS): per-shard sample size override.

    Exchange (see ExchangeConfig):
      exchange       "dense" | "dense_spill" | "ragged" | "allgather".
      pair_factor    dense: per-(src,dst) capacity multiplier.
      out_slack      output-buffer slack on the (1+eps) capacity.

    Overflow policy (DESIGN.md Section 8):
      on_overflow    what happens when an exchange capacity would drop keys.
                     "raise": current/default behavior — `sort()` surfaces
                     the device-side overflow counter for the caller to
                     check; `argsort`/`sort_kv` materialize it (one host
                     sync) and raise, because a truncated permutation is
                     silent corruption. "retry": the overflow counter is
                     materialized once per launch and, when nonzero, the
                     sort re-runs with `capacity_scale` doubled per attempt
                     (pair caps, out caps, AND sample caps — every static
                     buffer) and splitters warm-started from the failed
                     attempt's converged state; after `max_overflow_retries`
                     escalations a final attempt runs on the spill channel,
                     and only if even that truncates does it raise. "spill":
                     a trace-time swap of the dense exchange for the
                     dense_spill channel (exact for send-side overflow) —
                     nothing to check at runtime, so the happy path does
                     ZERO host syncs even for argsort (exactness is
                     verified from the gathered length, which is
                     materialized anyway).
      max_overflow_retries  bounded escalation attempts for "retry".
      capacity_scale uniform static-buffer multiplier (pair/out/sample
                     caps). Callers normally leave this at 1.0; the retry
                     policy sweeps it 2, 4, 8, ... internally.

    Verification policy (DESIGN.md Section 9):
      verify         device-side postcondition audit fused into the launch
                     (repro.sort.verify). "off" (default): no audit, zero
                     cost. "cheap": 2-lane (64-bit) multiset fingerprint
                     input-vs-output + per-shard sortedness + cross-shard
                     boundary/range checks + count conservation, one extra
                     fused psum and one ppermute, one host sync per launch
                     to judge the verdict. "full": same audit with 4
                     fingerprint lanes (128 bits).
      on_verify_failure  what a failed audit does. "raise": typed
                     VerificationError (BatchVerificationError on the
                     batched path, carrying per-row verdicts so serving
                     can fail only corrupted rows). "retry": re-run once —
                     transient corruption recovers — then escalate to the
                     fallback configuration before raising. "fallback":
                     re-run directly on the maximally-conservative path
                     (spill-channel exchange + kernel_policy="xla"),
                     raising only if even that fails its audit. Attempts
                     are recorded on `RecoveryStats`.
      imbalance_slo  partition-quality SLO: when set, `sort()` enforces
                     achieved_imbalance = max_shard_load / (N/p) <= this
                     bound host-side (counts are materialized by the
                     verdict/gather anyway). Exceeded, it auto-recovers —
                     duplicate tagging first (duplicate pileups are the
                     usual cause), then bonus refinement (doubled
                     splitter sampling/rounds) — and raises a typed
                     ImbalanceError only when both fail. None: record
                     achieved_imbalance (whenever verify != "off") but
                     never enforce. Typical value: 1 + eps.

    Placement:
      mesh           jax Mesh to sort over (None => 1-D mesh over all devices).
      axis_name      mesh axis of 1-D algorithms.
      outer_axis / inner_axis  multistage: the two nested mesh axes. When
                     `mesh` is None the driver factors p into (r1, r2) itself.

    Batched execution (DESIGN.md Section 6):
      batch          True => `sort()` accepts a (B, n) array of B
                     independent requests and routes it through the batched
                     single-launch engine (`repro.sort.sort_batched`): one
                     shard_map launch, one all_gather + one psum per
                     splitter round and one all_to_all for the dense
                     exchange regardless of B, plus the compiled-executable
                     cache. `sort_batched` itself ignores this flag.

    Semisort (repro.sort.semisort; DESIGN.md Section 10):
      semisort_sample   per-shard sample rows for heavy-hitter detection in
                     `semisort`/`groupby_aggregate`. 0 = auto-size from
                     (n_local, p). Ignored by `sort()`.
      heavy_fraction classify a key as heavy when its estimated global
                     frequency reaches heavy_fraction * N / p — heavy keys
                     bypass the splitter/exchange path entirely and are
                     reported as (key, count) aggregates; everything else
                     rides the light (splitter histogram) path.

    Semantics:
      stable         True => implicit duplicate tagging (paper Sec. 6.3) is
                     applied so equal keys keep input order and original
                     indices travel with the keys. `argsort`/`sort_kv` force
                     this on. False + tag=None still auto-tags when the input
                     is detected to contain duplicates and a tag fits.
      tag            tri-state tagging override: None = auto (tag when stable,
                     when indices are required, or when duplicates are
                     detected and a tag fits), True = always, False = never
                     (caller asserts distinct keys). Auto-detection runs only
                     where a tag could be packed (key range bits + tag bits
                     within 30, or 62 under jax x64): there it costs one
                     single-placement O(n log n) device sort up front — at
                     production scale pass an explicit True/False instead.
      seed           PRNG seed for the sampling rounds.
      initial_probes warm-start probes (the ChaNGa trick, paper Sec. 7.3).
      local_sort_fn  local-sort callable override; None routes the local sort
                     through the kernel dispatch layer under kernel_policy.

    Compute backend:
      kernel_policy  "auto" | "pallas" | "xla" — which backend runs the
                     local sort, probe ranking, and post-exchange merges
                     (repro.kernels.dispatch, DESIGN.md Section 2.5).
                     "auto" = Pallas kernels on TPU, XLA primitives
                     elsewhere; "pallas" forces the kernels (interpret mode
                     off-TPU — the parity/testing path); "xla" forces the
                     jnp primitives. All choices are bit-identical.
    """

    algorithm: str = "hss"
    eps: float = 0.05
    # splitter determination
    rounds: int = 0
    sample_per_shard: int = 0
    adaptive: bool = True
    total_sample: int | None = None
    s: int | None = None
    # exchange
    exchange: str = "dense"
    pair_factor: float = 3.0
    out_slack: float = 1.0
    # overflow policy
    on_overflow: str = "raise"
    max_overflow_retries: int = 3
    capacity_scale: float = 1.0
    # verification policy
    verify: str = "off"
    on_verify_failure: str = "raise"
    imbalance_slo: float | None = None
    # placement
    mesh: Any = None
    axis_name: str = "sort"
    outer_axis: str = "outer"
    inner_axis: str = "inner"
    # batched execution
    batch: bool = False
    # semisort (repro.sort.semisort; DESIGN.md Section 10)
    semisort_sample: int = 0
    heavy_fraction: float = 0.5
    # semantics
    stable: bool = False
    tag: bool | None = None
    kernel_policy: str = "auto"
    seed: int = 0
    initial_probes: Any = None
    local_sort_fn: Any = None

    def __post_init__(self):
        if self.on_overflow not in ON_OVERFLOW:
            raise ValueError(
                f"on_overflow must be one of {ON_OVERFLOW}, "
                f"got {self.on_overflow!r}")
        if self.verify not in VERIFY:
            raise ValueError(
                f"verify must be one of {VERIFY}, got {self.verify!r}")
        if self.on_verify_failure not in ON_VERIFY_FAILURE:
            raise ValueError(
                f"on_verify_failure must be one of {ON_VERIFY_FAILURE}, "
                f"got {self.on_verify_failure!r}")
        if self.imbalance_slo is not None and self.imbalance_slo < 1.0:
            raise ValueError(
                f"imbalance_slo is max_shard_load/(N/p), necessarily >= 1; "
                f"got {self.imbalance_slo!r}")

    def resolved_exchange(self) -> str:
        """The exchange strategy after the overflow policy is applied:
        "spill" swaps the capacity-dropping dense channel for the exact
        dense_spill channel at trace time (the already-exact ragged and
        allgather strategies are left alone)."""
        if self.on_overflow == "spill" and self.exchange == "dense":
            return "dense_spill"
        return self.exchange

    def overflow_structurally_zero(self) -> bool:
        """True when the traced program cannot drop keys on the send side
        and the (1+eps) guarantee sizes the receive buffers — i.e. the
        overflow counter needs no host-blocking check on the happy path.
        dense_spill can still truncate at out_cap under a violated eps
        guarantee; permutation front-doors re-verify from the gathered
        length (already host-side) instead of syncing the counter."""
        return self.resolved_exchange() in ("ragged", "dense_spill",
                                            "allgather")

    def hss_config(self) -> HSSConfig:
        return HSSConfig(eps=self.eps, rounds=self.rounds,
                         sample_per_shard=self.sample_per_shard,
                         adaptive=self.adaptive, out_slack=self.out_slack,
                         capacity_scale=self.capacity_scale,
                         kernel_policy=self.kernel_policy)

    def exchange_config(self) -> ExchangeConfig:
        return ExchangeConfig(strategy=self.resolved_exchange(),
                              pair_factor=self.pair_factor,
                              out_slack=self.out_slack,
                              capacity_scale=self.capacity_scale,
                              kernel_policy=self.kernel_policy)
