"""HSS splitter determination (the paper's core contribution, Section 4).

The algorithm maintains, for every target splitter rank t_i = N*i/p, a
*splitter interval*: the tightest pair of already-ranked keys bracketing t_i.
Each round samples keys inside the union of the (still unsatisfied) splitter
intervals, ranks the sample exactly with one histogram reduction, and tightens
every interval. Lemmas 4.4/4.5 give geometric shrinkage of the union, so a
constant per-round sample suffices (Theorem 4.8).

TPU/JAX adaptation (DESIGN.md Section 2):
  * no central processor: samples are all_gather'ed, histograms psum'ed, and
    the (tiny) interval state is maintained replicated on every shard;
  * Bernoulli sampling uses fixed-capacity sentinel-padded sample buffers so
    all shapes are static; overflow is counted and surfaced;
  * rank bookkeeping is exact: the "histogram" is the vector of global ranks
    of the probes (number of keys < probe), obtained by psum-ing local rank
    vectors. The local ranking runs through repro.kernels.dispatch: the
    Pallas probe-count kernel on TPU (it counts rather than searches, so it
    can also rank shards that are not sorted yet), searchsorted over the
    locally sorted shard on the XLA path — bit-identical results.

Everything here runs *inside* shard_map over one mesh axis (`axis_name`).
Pure helpers (refine, membership, choice) are also reused verbatim by the
logical-p simulator in repro.core.simulator.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import jax.random as jr

from repro.core.common import (
    HSSConfig,
    hi_sentinel,
    interval_union_size,
    lo_sentinel,
    sampling_ratios,
)
from repro.kernels import dispatch
from repro.runtime import spans


#: Collectives one non-converged HSS round issues — ONE all_gather of the
#: sample buffer and ONE fused psum carrying the histogram + the
#: (n_sample, overflow) scalars. The static-analysis contracts
#: (repro.analysis.contracts) pin the round-scan body to exactly this;
#: adding a collective to `do_round` without updating the contract fails
#: `repro.analysis.lint`.
ROUND_COLLECTIVES = {"all_gather": 1, "psum": 1}


class SplitterState(NamedTuple):
    """Replicated per-splitter interval state; arrays of shape (p-1,).

    lo_rank/hi_rank are *raw* monotone bounds (never collapsed), so
    searchsorted-based membership tests stay valid. `satisfied` marks splitters
    whose target range T_i already contains a ranked key.
    """

    lo_rank: jax.Array  # int32, largest known rank <= t_i
    hi_rank: jax.Array  # int32, smallest known rank >= t_i
    lo_key: jax.Array   # key at lo_rank (lo sentinel when rank 0 / unknown)
    hi_key: jax.Array   # key at hi_rank (hi sentinel when rank N / unknown)
    satisfied: jax.Array  # bool


class SplitterStats(NamedTuple):
    """Per-round diagnostics, arrays of shape (k,)."""

    gamma_size: jax.Array      # |gamma_{j-1}|: union of active intervals before round j
    sample_count: jax.Array    # total keys sampled in round j (all shards)
    overflow: jax.Array        # samples dropped due to buffer capacity
    n_satisfied: jax.Array     # satisfied splitters after round j
    rounds_used: jax.Array     # scalar: first round after which all satisfied (1-based)


#: Where a batched launch (`repro.sort.driver.run_batched`, a `jax.vmap`
#: of the single-request pipeline) puts the batch axis of each stats field:
#: per-round arrays become (k, B), `rounds_used` (B,).
BATCH_AXES = SplitterStats(gamma_size=1, sample_count=1, overflow=1,
                           n_satisfied=1, rounds_used=0)


def splitter_targets(n: int, p: int) -> jax.Array:
    """Target ranks t_i = N*i/p for i = 1..p-1."""
    import numpy as np
    return jnp.asarray(np.arange(1, p, dtype=np.int64) * n // p, jnp.int32)


def init_state(p: int, n: int, dtype) -> SplitterState:
    m = p - 1
    return SplitterState(
        lo_rank=jnp.zeros((m,), jnp.int32),
        hi_rank=jnp.full((m,), n, jnp.int32),
        lo_key=jnp.full((m,), lo_sentinel(dtype), dtype),
        hi_key=jnp.full((m,), hi_sentinel(dtype), dtype),
        satisfied=jnp.zeros((m,), bool),
    )


def _pick(mask, a, b):
    """jnp.where over keys that may be tuples: a (m,) mask picks whole
    (m, words) rows of lexicographic keys (repro.core.splitters, records)."""
    if a.ndim > mask.ndim:
        mask = mask[:, None]
    return jnp.where(mask, a, b)


def refine(state: SplitterState, probes: jax.Array, probe_ranks: jax.Array,
           targets: jax.Array, tol) -> SplitterState:
    """Tighten every splitter interval with freshly ranked probes.

    probes must be sorted ascending (sentinel-padded tail) and probe_ranks
    nondecreasing (sentinels rank N). Fully vectorized over the p-1 splitters.
    """
    j = jnp.searchsorted(probe_ranks, targets, side="left")  # first rank >= t
    j = jnp.minimum(j, probe_ranks.shape[0] - 1)
    cand_hi_rank = probe_ranks[j]
    cand_hi_key = probes[j]
    jm = jnp.maximum(j - 1, 0)
    has_lo = j > 0
    cand_lo_rank = jnp.where(has_lo, probe_ranks[jm], 0)
    cand_lo_key = _pick(has_lo, probes[jm], state.lo_key)

    take_lo = cand_lo_rank > state.lo_rank
    take_hi = cand_hi_rank < state.hi_rank
    lo_rank = jnp.where(take_lo, cand_lo_rank, state.lo_rank)
    lo_key = _pick(take_lo, cand_lo_key, state.lo_key)
    hi_rank = jnp.where(take_hi, cand_hi_rank, state.hi_rank)
    hi_key = _pick(take_hi, cand_hi_key, state.hi_key)
    satisfied = ((targets - lo_rank) <= tol) | ((hi_rank - targets) <= tol)
    return SplitterState(lo_rank, hi_rank, lo_key, hi_key, satisfied)


def active_union_size(state: SplitterState, targets: jax.Array) -> jax.Array:
    """|gamma|: union (rank space) of intervals of *unsatisfied* splitters.

    Satisfied splitters contribute empty [t_i, t_i] intervals. Because the raw
    bounds are monotone and intervals are disjoint-or-identical (paper
    Section 4.2.2), the substitution only ever undercounts overlap slivers,
    which is conservative (drives the sampling probability up slightly).
    """
    lo = jnp.where(state.satisfied, targets, state.lo_rank)
    hi = jnp.where(state.satisfied, targets, state.hi_rank)
    return interval_union_size(lo, hi)


def gamma_membership(x: jax.Array, state: SplitterState) -> jax.Array:
    """Boolean mask: which keys of sorted-or-not x lie in an active interval.

    A key x is in gamma iff some unsatisfied splitter i has
    lo_key_i < x < hi_key_i. The containing intervals form a contiguous run
    [a, b) over i (intervals are disjoint-or-identical and bounds monotone), so
    membership reduces to two searchsorteds plus a prefix-sum lookup.
    """
    unsat = (~state.satisfied).astype(jnp.int32)
    csum = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(unsat)])
    a = jnp.searchsorted(state.hi_key, x, side="right")   # first i with hi > x
    b = jnp.searchsorted(state.lo_key, x, side="left")    # first i with lo >= x
    b = jnp.maximum(a, b)
    return (csum[b] - csum[a]) > 0


def choose_splitters(state: SplitterState, targets: jax.Array):
    """Final splitter keys: the closer satisfied side of each interval."""
    d_lo = targets - state.lo_rank
    d_hi = state.hi_rank - targets
    pick_lo = d_lo <= d_hi
    keys = _pick(pick_lo, state.lo_key, state.hi_key)
    ranks = jnp.where(pick_lo, state.lo_rank, state.hi_rank)
    return keys, ranks


def _sample_round(local_sorted, state, prob, cap, rng, kernel_policy="auto"):
    """Bernoulli-sample active-interval keys into a fixed sentinel-padded buffer."""
    n_local = local_sorted.shape[0]
    in_g = gamma_membership(local_sorted, state)
    u = jr.uniform(rng, (n_local,))
    mask = in_g & (u < prob)
    n_hit = jnp.sum(mask.astype(jnp.int32))
    vals = jnp.where(mask, local_sorted, hi_sentinel(local_sorted.dtype))
    vals = dispatch.local_sort(vals, policy=kernel_policy)[:cap]
    overflow = jnp.maximum(n_hit - cap, 0)
    return vals, n_hit - overflow, overflow


class ShardKeys(NamedTuple):
    """How the splitter rounds see one shard's sorted keys: scalar keys
    (`hss_splitters`) or lexicographic tuples of words (`hss_splitters_lex`).

    init(n)            the initial SplitterState for N = n keys
    sample(state, prob, cap, rng) -> (vals, n_sampled, overflow): a
                       sentinel-padded buffer of cap sampled active keys
    gather(vals)       every shard's sample, end to end
    sort(probes)       the gathered sample, sorted
    ranks(probes)      this shard's count of keys below each probe
    """

    init: Callable
    sample: Callable
    gather: Callable
    sort: Callable
    ranks: Callable


def hss_splitters(
    local_sorted: jax.Array,
    *,
    axis_name: str,
    p: int,
    cfg: HSSConfig,
    rng: jax.Array,
    initial_probes: jax.Array | None = None,
    shard_keys: ShardKeys | None = None,
):
    """Determine the p-1 splitters of a distributed sort. shard_map-resident.

    Args:
      local_sorted: this shard's keys, sorted ascending, shape (n_local,).
      axis_name: mesh axis over which the p shards live.
      p: number of shards on that axis (static).
      cfg: HSSConfig.
      rng: per-shard PRNG key (callers fold in jax.lax.axis_index(axis_name)).
      initial_probes: optional sorted probe keys to warm-start round 1 with
        (e.g. the previous iteration's splitters — the ChaNGa trick, paper
        Section 7.3). Sentinel-padded, any static length.
      shard_keys: how the rounds see the shard's keys; None for the scalar
        keys of `local_sorted` (`hss_splitters_lex` passes word tuples,
        and `local_sorted` then only gives the shard's length).

    Returns:
      (splitter_keys (p-1,), splitter_ranks (p-1,), SplitterStats) — replicated.
    """
    n_local = local_sorted.shape[0]
    if shard_keys is None:
        policy = cfg.kernel_policy
        shard_keys = ShardKeys(
            init=functools.partial(init_state, p, dtype=local_sorted.dtype),
            sample=functools.partial(_sample_round, local_sorted,
                                     kernel_policy=policy),
            gather=functools.partial(jax.lax.all_gather,
                                     axis_name=axis_name, tiled=True),
            sort=functools.partial(dispatch.local_sort, policy=policy),
            ranks=functools.partial(dispatch.probe_ranks, local_sorted,
                                    policy=policy, assume_sorted=True))
    n = n_local * p
    k = cfg.resolved_rounds(p)
    cap = cfg.resolved_sample_cap(p)
    tol = jnp.int32(max(1, int(n * cfg.eps / (2 * p))))
    targets = splitter_targets(n, p)
    f_total = float(cap * p) / 2.0  # target expected overall sample per round
    ratios = jnp.asarray(sampling_ratios(p, cfg.eps, k), jnp.float32)

    state0 = shard_keys.init(n)
    if initial_probes is not None:
        # Free warm-start: rank the provided probes once and refine.
        pr = jax.lax.psum(shard_keys.ranks(initial_probes), axis_name)
        state0 = refine(state0, initial_probes, pr, targets, tol)

    def round_body(carry, j):
        state, key = carry
        key, sub = jr.split(key)
        gamma = active_union_size(state, targets)
        if cfg.adaptive:
            prob = jnp.minimum(1.0, f_total / jnp.maximum(gamma, 1).astype(jnp.float32))
        else:
            prob = jnp.minimum(1.0, ratios[j] / float(n_local))

        def do_round(state):
            vals, n_samp, ovf = shard_keys.sample(state, prob, cap, sub)
            probes = shard_keys.sort(shard_keys.gather(vals))
            local_ranks = shard_keys.ranks(probes)
            # one fused reduction per round: ranks + sample count + overflow
            # (explicit int32: under x64 jnp.sum promotes counts to int64,
            # which would leak into the scan carry through refine)
            packed = jax.lax.psum(
                jnp.concatenate(
                    [local_ranks,
                     jnp.stack([n_samp, ovf]).astype(jnp.int32)]),
                axis_name)
            state = refine(state, probes, packed[:-2], targets, tol)
            return state, packed[-2], packed[-1]

        def skip_round(state):
            return state, jnp.int32(0), jnp.int32(0)

        # Early exit: once every splitter is satisfied, later rounds skip
        # sampling/sorting/ranking entirely (the state cannot improve the
        # already-met tolerance; it can only shave |t_i - rank| further,
        # which the exchange does not need). `satisfied` is replicated, so
        # every shard takes the same branch — no collective divergence.
        state, cnt, ovf = jax.lax.cond(
            jnp.all(state.satisfied), skip_round, do_round, state)
        stats = (
            gamma,
            cnt,
            ovf,
            jnp.sum(state.satisfied.astype(jnp.int32)),
        )
        return (state, key), stats

    with spans.scope(spans.SPLITTER_ROUND):   # the rounds and their loop
        (state, _), (gam, cnt, ovf, nsat) = jax.lax.scan(
            round_body, (state0, rng), jnp.arange(k))
    keys, ranks = choose_splitters(state, targets)
    all_sat = nsat >= (p - 1)
    rounds_used = jnp.where(
        jnp.any(all_sat), 1 + jnp.argmax(all_sat), jnp.int32(k))
    stats = SplitterStats(gam, cnt, ovf, nsat, rounds_used)
    return keys, ranks, stats


# -- lexicographic keys: tuples of unsigned words (records) -----------------
#
# A key is a tuple of uint32 words compared lexicographically; a shard
# holds it as `words` planes of shape (n_local,), sorted. The last word is
# the record's global index, so tuples are distinct even where the
# records' keys are equal, and HSS's eps balance holds however many
# records share a key. Splitter state keeps (p-1, words) key rows; the
# all-ones tuple is the hi sentinel, above every real tuple because no
# global index reaches 2^32 - 1.

def lex_less(a, b, or_equal: bool = False):
    """Whether word tuple a sorts before b (or equals it, with or_equal):
    a, b are sequences of broadcastable uint32 arrays, most significant
    word first."""
    res = jnp.asarray(or_equal)
    for x, y in reversed(list(zip(a, b))):
        res = (x < y) | ((x == y) & res)
    return res


def lex_searchsorted(planes, queries):
    """For each query row of (m, words), how many tuples of the sorted
    planes lie below it (searchsorted side="left"): a binary search of
    bit_length(n) steps, unrolled (no loop op in the program), each
    reading m tuples."""
    n = planes[0].shape[0]
    m = queries.shape[0]
    q = [queries[:, w] for w in range(queries.shape[1])]
    lo = jnp.zeros((m,), jnp.int32)
    hi = jnp.full((m,), n, jnp.int32)
    for _ in range(max(1, n.bit_length())):
        mid = (lo + hi) // 2
        at = jnp.minimum(mid, n - 1)
        below = lex_less([pl[at] for pl in planes], q)
        open_ = lo < hi
        lo, hi = (jnp.where(open_ & below, mid + 1, lo),
                  jnp.where(open_ & ~below, mid, hi))
    return lo


def lex_sort_rows(rows):
    """Sort (m, words) key rows lexicographically."""
    cols = jax.lax.sort(tuple(rows[:, w] for w in range(rows.shape[1])),
                        num_keys=rows.shape[1])
    return jnp.stack(cols, axis=1)


def init_state_lex(p: int, n: int, words: int) -> SplitterState:
    m = p - 1
    return SplitterState(
        lo_rank=jnp.zeros((m,), jnp.int32),
        hi_rank=jnp.full((m,), n, jnp.int32),
        lo_key=jnp.zeros((m, words), jnp.uint32),
        hi_key=jnp.full((m, words), hi_sentinel(jnp.uint32), jnp.uint32),
        satisfied=jnp.zeros((m,), bool),
    )


def gamma_membership_lex(planes, state: SplitterState) -> jax.Array:
    """`gamma_membership` for word tuples: whether each key lies strictly
    inside the interval of some unsatisfied splitter, one elementwise pass
    over the planes per splitter."""
    inside = jnp.zeros(planes[0].shape, bool)
    for i in range(state.lo_key.shape[0]):
        lo = [state.lo_key[i, w] for w in range(len(planes))]
        hi = [state.hi_key[i, w] for w in range(len(planes))]
        inside = inside | (~state.satisfied[i] & lex_less(lo, planes)
                           & lex_less(planes, hi))
    return inside


def _sample_round_lex(planes, state, prob, cap, rng):
    """Bernoulli-sample active-interval tuples into a (cap, words) buffer.
    The shard is sorted, so the first cap sampled positions, in order,
    are the sample: one sort of n_local positions finds them, and cap
    rows are read."""
    n_local = planes[0].shape[0]
    mask = gamma_membership_lex(planes, state) & (
        jr.uniform(rng, (n_local,)) < prob)
    n_hit = jnp.sum(mask.astype(jnp.int32))
    pos = jnp.sort(jnp.where(mask, jnp.arange(n_local, dtype=jnp.int32),
                             n_local))[:cap]
    at = jnp.minimum(pos, n_local - 1)
    vals = jnp.stack([jnp.where(pos < n_local, pl[at], hi_sentinel(pl.dtype))
                      for pl in planes], axis=1)
    overflow = jnp.maximum(n_hit - cap, 0)
    return vals, n_hit - overflow, overflow


def hss_splitters_lex(planes, *, axis_name: str, p: int, cfg: HSSConfig,
                      rng: jax.Array):
    """`hss_splitters` over lexicographic keys: `planes` is a tuple of
    (n_local,) uint32 words, sorted as tuples, the last of them each key's
    global index. The same rounds, sample sizes and eps; each round ranks
    its probes with a binary search per probe (scope `probe_search`) where
    scalar keys use the histogram kernel. Returns (splitter_keys
    (p-1, words), splitter_ranks (p-1,), SplitterStats)."""
    planes = tuple(planes)

    def ranks(probes):
        with spans.scope(spans.PROBE_SEARCH):
            return lex_searchsorted(planes, probes)

    keys = ShardKeys(
        init=functools.partial(init_state_lex, p, words=len(planes)),
        sample=functools.partial(_sample_round_lex, planes),
        gather=functools.partial(jax.lax.all_gather, axis_name=axis_name,
                                 tiled=True),
        sort=lex_sort_rows,
        ranks=ranks)
    return hss_splitters(planes[0], axis_name=axis_name, p=p, cfg=cfg,
                         rng=rng, shard_keys=keys)


def heavy_candidates(sample_sorted: jax.Array, *, max_heavy: int,
                     min_count: int) -> jax.Array:
    """Heavy-hitter candidates from a sorted, sentinel-padded sample buffer.

    A key is a candidate when its sample run length reaches `min_count`
    (the semisort heavy/light split: a key sampled that often has, w.h.p.,
    global frequency above the detection threshold). Returns a (max_heavy,)
    ascending buffer of distinct candidate keys, hi-sentinel padded; the
    hi-sentinel pad values of the sample itself are never candidates.

    Pure shard-local math over replicated inputs — callers gather the
    per-shard sample buffers first, so every shard computes the identical
    candidate set (the replication invariant the exchange seam relies on).
    """
    sent = hi_sentinel(sample_sorted.dtype)
    idx = jnp.arange(sample_sorted.shape[0], dtype=jnp.int32)
    ll = jnp.searchsorted(sample_sorted, sample_sorted, side="left")
    rr = jnp.searchsorted(sample_sorted, sample_sorted, side="right")
    is_head = ((idx == ll.astype(jnp.int32))
               & ((rr - ll) >= min_count)
               & (sample_sorted != sent))
    compact = jnp.sort(jnp.where(is_head, sample_sorted, sent))
    return compact[:max_heavy]
