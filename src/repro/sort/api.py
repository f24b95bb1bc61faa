"""The unified sort front-door: `sort`, `argsort`, `sort_kv`.

One entry point over every partitioning strategy in the repo (DESIGN.md
Section 3). Callers pick an algorithm with `SortSpec(algorithm=...)` and the
adapter layer takes care of float keys, duplicates, payload permutation, and
ragged input lengths — none of which the raw `repro.core` entry points
handle for you.

    from repro.sort import SortSpec, sort, argsort, sort_kv

    out = sort(x)                                 # HSS, all devices
    out = sort(x, SortSpec(algorithm="ams", eps=0.1))
    out = sort(x, algorithm="sample_regular")     # kwargs build the spec
    order = argsort(x)                            # stable, duplicate-safe
    keys, vals = sort_kv(lengths, doc_ids)        # payloads ride along
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ams import ams_sample_size
from repro.core.common import RECORD_BYTES
from repro.core.sample_sort import default_regular_s, default_total_sample
from repro.kernels import dispatch
from repro.runtime import chaos, spans
from repro.sort import driver, verify
from repro.sort.adapters import (BatchedSortOutput, RecordSortOutput,
                                 SortOutput, local_sort_records, make_plan,
                                 pack_records)
from repro.sort.partitioners import ShardCtx, get_partitioner
from repro.sort.spec import SortSpec
from repro.sort.verify import (BatchVerificationError, ImbalanceError,
                               VerificationError)


@dataclasses.dataclass(frozen=True)
class RecoveryStats:
    """How the recovery policies resolved a sort (attached to the returned
    output as `.recovery`; None when no policy had anything to record).

    policy            the on_overflow policy that ran.
    attempts          total launches, 1 = first launch was already exact.
    escalations       capacity_scale of each re-launch, in order.
    spill_fallback    True when the final attempt used the spill channel.
    recovered_overflow  the overflow count of the first (failed) launch —
                      how many keys would have been dropped without the
                      policy.
    verify_failures   audits that FAILED across all launches (0 = the
                      audit, if any, passed first time).
    verify_retries    re-launches the on_verify_failure="retry" policy
                      spent.
    verify_fallback   True when a failed audit was retried on the
                      conservative fallback path (spill + xla kernels).
    achieved_imbalance  max_shard_load / (N/p) of the served output (the
                      paper's (1+eps) quantity, worst row on the batched
                      path); recorded whenever verify != "off" or an
                      imbalance_slo is set.
    imbalance_recovery  None, or how an imbalance-SLO violation was
                      auto-recovered: "tag" (duplicate tagging) or
                      "refine" (bonus splitter refinement).
    """

    policy: str
    attempts: int
    escalations: tuple
    spill_fallback: bool
    recovered_overflow: int
    verify_failures: int = 0
    verify_retries: int = 0
    verify_fallback: bool = False
    achieved_imbalance: float | None = None
    imbalance_recovery: str | None = None


def _as_spec(spec, overrides) -> SortSpec:
    if spec is None:
        return SortSpec(**overrides)
    if not isinstance(spec, SortSpec):
        raise TypeError(f"spec must be a SortSpec, got {type(spec)}")
    return dataclasses.replace(spec, **overrides) if overrides else spec


def _mesh_axes(spec: SortSpec, part):
    p = spec.mesh.devices.size if spec.mesh is not None else len(jax.devices())
    axes = part.mesh_axes(spec, p)
    return p, tuple(a for a, _ in axes), tuple(s for _, s in axes)


def _mesh_fingerprint(spec: SortSpec):
    """Structural mesh identity: a fresh-but-equal Mesh still hits."""
    if spec.mesh is None:
        return ("auto", len(jax.devices()), jax.default_backend())
    return (tuple((a, int(s)) for a, s in spec.mesh.shape.items()),
            tuple(int(d.id) for d in spec.mesh.devices.flat))


def _spec_trace_fields(spec: SortSpec) -> tuple:
    """The SortSpec fields that shape the traced program (everything else
    is either a runtime argument, like the seed, or captured through the
    encoded array's shape/dtype). The chaos trace token rides along: an
    active fault plan that clamps exchange capacities changes the trace,
    and a clamped executable must never be served from — or poison — the
    unclamped cache line (repro.runtime.chaos)."""
    return (spec.algorithm, spec.eps, spec.rounds, spec.sample_per_shard,
            spec.adaptive, spec.total_sample, spec.s,
            spec.resolved_exchange(), spec.pair_factor, spec.out_slack,
            spec.capacity_scale, spec.kernel_policy, spec.verify,
            spec.semisort_sample, spec.heavy_fraction,
            chaos.trace_token())


def spec_fingerprint(spec: SortSpec):
    """Hashable fingerprint of every SortSpec field that determines a
    request's served bits: the trace-shaping fields plus the semantic ones
    (stable/tag change the adapter plan, the seed changes the sampled
    splitters) and the structural mesh identity. Returns None when the
    spec carries opaque state no fingerprint can capture (a caller
    `local_sort_fn` or warm-start probes) — such specs must not share a
    cached executable or a serving batch with anything else."""
    if spec.local_sort_fn is not None or spec.initial_probes is not None:
        return None
    return _spec_trace_fields(spec) + (
        spec.stable, spec.tag, spec.seed, spec.on_verify_failure,
        spec.imbalance_slo, _mesh_fingerprint(spec))


def bucket_key(n, dtype, spec: SortSpec, *, kind: str = "sort", param=None):
    """Serving-batch grouping key (repro.serve): requests that share it
    can stack into one `sort_batched` launch — same length, key dtype,
    request kind, and full spec fingerprint — and therefore share one
    compiled-executable cache entry per batch size. This is the public
    face of `_cache_key`'s derivation: the exec-cache key proper also
    hashes the *encoded* array shape/dtype, which is only known once a
    batch's adapter plan is built, so the batcher groups on everything
    known pre-encoding. Opaque specs (local_sort_fn / initial_probes)
    bucket by object identity: they never share a batch.

    `param` carries a kind-specific scalar that shapes the launch (the k
    of a `top_k` request): requests with different k must not stack.
    None (every other kind) leaves the key shape unchanged, so existing
    buckets and any persisted key fingerprints are unaffected."""
    fp = spec_fingerprint(spec)
    if fp is None:
        fp = ("opaque", id(spec))
    key = (kind, int(n), str(jnp.dtype(dtype)), fp)
    return key if param is None else key + (param,)


def _cache_key(spec: SortSpec, names, sizes, enc, *, kind: str):
    """Compiled-executable cache key: (kind — single, batched or records —,
    shape bucket, dtype, SortSpec fingerprint, mesh fingerprint). None
    (uncached) when the spec carries state the key cannot capture — a
    caller-supplied local_sort_fn or warm-start probes would be baked into
    a reused trace."""
    if spec.local_sort_fn is not None or spec.initial_probes is not None:
        return None
    return ((kind,) + _spec_trace_fields(spec)
            + (names, sizes, _mesh_fingerprint(spec),
               tuple(enc.shape), str(enc.dtype)))


def _sort_impl(x, spec: SortSpec, want_indices: bool) -> SortOutput:
    part = get_partitioner(spec.algorithm)
    x = jnp.asarray(x)
    if x.ndim != 1:
        raise ValueError(f"sort expects a 1-D key array, got shape {x.shape}")
    p, names, sizes = _mesh_axes(spec, part)

    with spans.span(spans.PLAN):
        plan = make_plan(x, spec, p, want_indices=want_indices)
    enc = plan.encode(x)
    probes = (plan.encode_probes(spec.initial_probes)
              if spec.initial_probes is not None else None)
    ctx = ShardCtx(spec=spec, axis_names=names, sizes=sizes, rng=None,
                   initial_probes=probes)
    p1_sort = spec.local_sort_fn or dispatch.local_sort_fn(spec.kernel_policy)
    sort_fn = lambda local, rng: part.sharded(local, rng, ctx)
    cache_key = _cache_key(spec, names, sizes, enc, kind="single")
    audit = spec.verify != "off" and p > 1
    if audit:
        corrupt = chaos.corrupt_now()
        if corrupt is not None:
            cache_key = None   # a corrupted executable must never be cached
        sort_fn = verify.audited(sort_fn, tier=spec.verify, axis_names=names,
                                 sizes=sizes, batched=False, corrupt=corrupt)
    raw = driver.run(
        sort_fn,
        enc, mesh=spec.mesh, axis_names=names, sizes=sizes, seed=spec.seed,
        n_real=plan.n, local_sort_fn=p1_sort, cache_key=cache_key)
    audit_vec = None
    if audit:
        raw, audit_vec = verify.split_raw(raw)
    elif spec.verify != "off":   # p == 1 short-circuit bypasses sort_fn
        audit_vec = verify.audit_p1(enc, raw[0], raw[1], spec.verify)
    with spans.span(spans.DECODE):
        out = plan.decode(raw)
    out._audit_vec = audit_vec
    out._audit_expected = plan.n + plan.n_pad
    return out


def _sort_batched_impl(xs, spec: SortSpec,
                       want_indices: bool) -> BatchedSortOutput:
    part = get_partitioner(spec.algorithm)
    if xs.ndim != 2:
        raise ValueError(
            f"sort_batched expects a (B, n) key array, got shape {xs.shape}")
    p, names, sizes = _mesh_axes(spec, part)

    with spans.span(spans.PLAN):
        plan = make_plan(xs, spec, p, want_indices=want_indices)
    enc = plan.encode(xs)
    probes = (plan.encode_probes(spec.initial_probes)
              if spec.initial_probes is not None else None)
    ctx = ShardCtx(spec=spec, axis_names=names, sizes=sizes, rng=None,
                   initial_probes=probes)
    p1_sort = (jax.vmap(spec.local_sort_fn) if spec.local_sort_fn is not None
               else dispatch.local_sort_batched_fn(spec.kernel_policy))
    sort_fn = lambda local, rng: part.sharded(local, rng, ctx)
    row_args = ()
    if probes is not None and probes.ndim == 2:   # one probe row a request
        sort_fn = lambda local, rng, pr: part.sharded(
            local, rng, dataclasses.replace(ctx, initial_probes=pr))
        row_args = (probes,)
    cache_key = _cache_key(spec, names, sizes, enc, kind="batched")
    wrap = None
    if spec.verify != "off" and p > 1:
        corrupt = chaos.corrupt_now()
        if corrupt is not None:
            cache_key = None   # a corrupted executable must never be cached
        wrap = functools.partial(
            verify.audited, tier=spec.verify, axis_names=names, sizes=sizes,
            batched=True, corrupt=corrupt)
    raw = driver.run_batched(
        sort_fn,
        enc, mesh=spec.mesh, axis_names=names, sizes=sizes, seed=spec.seed,
        n_real=plan.n, local_sort_fn=p1_sort, cache_key=cache_key,
        row_args=row_args, wrap=wrap)
    audit_vec = None
    if wrap is not None:
        raw, audit_vec = verify.split_raw(raw)
    elif spec.verify != "off":   # p == 1 short-circuit bypasses sort_fn
        audit_vec = verify.audit_p1(enc, raw[0], raw[1], spec.verify)
    with spans.span(spans.DECODE):
        out = plan.decode_batched(raw)
    out._audit_vec = audit_vec
    out._audit_expected = plan.n + plan.n_pad
    return out


def _sort_batched_buckets(arrs, spec: SortSpec) -> list:
    """List-of-arrays input: length-bucket, one single-launch batch per
    distinct length, results back in input order as SortOutput views."""
    from repro.sort.grouping import group_by_length
    arrs = [jnp.asarray(a) for a in arrs]
    for a in arrs:
        if a.ndim != 1:
            raise ValueError(
                f"sort_batched list entries must be 1-D, got shape {a.shape}")
    results = [None] * len(arrs)
    for _, idxs in group_by_length(arrs).items():
        stacked = jnp.stack([arrs[i] for i in idxs])
        out = _with_policies(
            lambda s, xs=stacked: _sort_batched_impl(xs, s,
                                                     want_indices=False),
            spec, batched=True)
        for j, i in enumerate(idxs):
            results[i] = out.request(j)
    return results


def _host_overflow(out) -> int:
    """Materialize the overflow counter — the retry policy's one
    deliberate host sync per launch (max over the batch on the batched
    path, where `overflow` is (B,))."""
    with spans.span(spans.OVERFLOW_SYNC):
        return int(np.max(np.asarray(out.overflow)))


def _warm_started(spec: SortSpec, out) -> SortSpec:
    """Feed a failed attempt's converged splitters back in as warm-start
    probes, so the retry re-ranks p-1 known-good keys instead of sampling
    from scratch (the ChaNGa trick pointed at recovery). HSS only — it is
    the one partitioner that consumes probes."""
    if spec.algorithm != "hss":
        return spec
    sk = out.splitter_keys
    if sk is None or getattr(sk, "size", 0) == 0:
        return spec
    return dataclasses.replace(spec, initial_probes=sk)


def _with_overflow_policy(run, spec: SortSpec):
    """Execute `run(spec)` under the spec's overflow policy (DESIGN.md
    Section 8).

    "raise" and "spill" are trace-time-only policies: no counter is ever
    materialized here (spill swapped the exchange for the exact channel in
    `spec.exchange_config()`; raise leaves detection to the caller / the
    permutation front-doors' gathered-length check). "retry" materializes
    the counter once per launch and, while nonzero, re-runs with doubled
    `capacity_scale` and warm-started splitters; the final fallback
    attempt runs on the spill channel, so bounded escalation still ends
    exact unless even the (1+eps)-sized receive buffer truncates."""
    out = run(spec)
    if spec.on_overflow != "retry":
        return out
    ovf0 = _host_overflow(out)
    if ovf0 == 0:
        out.recovery = RecoveryStats("retry", 1, (), False, 0)
        return out
    esc = []
    for k in range(1, spec.max_overflow_retries + 1):
        scale = spec.capacity_scale * (2.0 ** k)
        esc.append(scale)
        out = run(dataclasses.replace(_warm_started(spec, out),
                                      capacity_scale=scale))
        if _host_overflow(out) == 0:
            out.recovery = RecoveryStats("retry", 1 + len(esc), tuple(esc),
                                         False, ovf0)
            return out
    fspec = dataclasses.replace(
        _warm_started(spec, out), on_overflow="spill",
        capacity_scale=esc[-1] if esc else spec.capacity_scale)
    out = run(fspec)
    left = _host_overflow(out)
    out.recovery = RecoveryStats("retry", 2 + len(esc), tuple(esc), True,
                                 ovf0)
    if left != 0:
        raise RuntimeError(
            f"sort overflow unrecovered after {len(esc)} capacity "
            f"escalations and a spill-channel fallback ({left} keys "
            "truncated at out_cap) — the splitting violated its eps "
            "guarantee; raise out_slack or eps")
    return out


def _update_recovery(out, spec: SortSpec, **fields) -> None:
    """Merge verify/imbalance results into the output's RecoveryStats,
    creating a baseline record when no overflow policy attached one."""
    base = out.recovery
    if base is None:
        base = RecoveryStats(spec.on_overflow, 1, (), False, 0)
    out.recovery = dataclasses.replace(base, **fields)


def _finalize_audit(out, spec: SortSpec):
    """Materialize a launch's audit vector into an AuditReport (the one
    deliberate host sync per verified launch) and attach it as
    `out.audit`. Returns None when the launch was not audited."""
    vec = getattr(out, "_audit_vec", None)
    if vec is None:
        return None
    batched = isinstance(out, BatchedSortOutput)
    report = verify.finalize(vec, tier=spec.verify,
                             n_expected=out._audit_expected, batched=batched)
    report.achieved_imbalance = _imbalance(out)
    out.audit = report
    return report


def _imbalance(out):
    """achieved_imbalance = max_shard_load / (N/p), per request on the
    batched path ((B,) array). Counts are already host-bound alongside the
    audit verdict, so this costs no extra launch."""
    counts = np.asarray(out.counts)
    p = counts.shape[-1]
    return counts.max(axis=-1).astype(np.float64) * p / float(out.n)


def _fallback_spec(spec: SortSpec) -> SortSpec:
    """The maximally-conservative configuration a failed audit falls back
    to: the exact spill exchange channel (dense -> dense_spill) and the
    plain XLA kernel path — sidestepping both the capacity-dropping
    exchange and a suspected kernel miscompile in one hop."""
    return dataclasses.replace(spec, on_overflow="spill",
                               kernel_policy="xla")


def _enforce_verify(inner, spec: SortSpec, out, *, batched: bool):
    """Apply `spec.on_verify_failure` to an audited output: judge the
    fused audit, and on failure walk retry -> fallback -> raise ("retry"),
    fallback -> raise ("fallback"), or raise immediately. Every attempt
    re-audits; the recovery trail lands on `out.recovery`."""
    report = _finalize_audit(out, spec)
    if report is None:
        return out
    failures = retries = 0
    fellback = False
    while not report.ok:
        failures += 1
        if spec.on_verify_failure == "retry" and retries == 0:
            retries = 1
            cand = inner(spec)
        elif spec.on_verify_failure in ("retry", "fallback") \
                and not fellback:
            fellback = True
            cand = inner(_fallback_spec(spec))
        else:
            _update_recovery(out, spec, verify_failures=failures,
                             verify_retries=retries,
                             verify_fallback=fellback,
                             achieved_imbalance=float(
                                 np.max(report.achieved_imbalance)))
            msg = report.describe()
            if batched:
                raise BatchVerificationError(msg, report, out)
            raise VerificationError(msg, report)
        report = _finalize_audit(cand, spec)
        out = cand
    _update_recovery(out, spec, verify_failures=failures,
                     verify_retries=retries, verify_fallback=fellback,
                     achieved_imbalance=float(
                         np.max(report.achieved_imbalance)))
    return out


def _refined_spec(spec: SortSpec, p: int, n_local: int) -> SortSpec:
    """Bonus-refinement configuration for the imbalance-SLO ladder:
    double the splitter-determination effort of whichever knob the
    algorithm actually samples with (plus two bonus histogram rounds for
    the HSS family, whose refinement is per-round)."""
    if spec.algorithm in ("hss", "multistage"):
        cfg = spec.hss_config()
        return dataclasses.replace(
            spec, rounds=cfg.resolved_rounds(p) + 2,
            sample_per_shard=2 * cfg.resolved_sample_cap(p))
    if spec.algorithm == "sample_regular":
        return dataclasses.replace(
            spec, s=2 * (spec.s or default_regular_s(p, spec.eps)))
    if spec.algorithm == "ams":
        base = spec.total_sample or ams_sample_size(p, spec.eps, n_local * p)
        return dataclasses.replace(spec, total_sample=2 * base)
    base = spec.total_sample or default_total_sample(p, n_local, spec.eps)
    return dataclasses.replace(spec, total_sample=2 * base)


def _enforce_slo(inner, spec: SortSpec, out, *, batched: bool):
    """Partition-quality SLO: record achieved_imbalance whenever it is
    already materialized (verify on, or an SLO set) and, when it exceeds
    `spec.imbalance_slo`, auto-recover — duplicate tagging first (the
    usual cause is a duplicate pileup the untagged splitters cannot cut),
    then bonus refinement — raising ImbalanceError only when both fail."""
    slo = spec.imbalance_slo
    if slo is None and spec.verify == "off":
        return out
    worst = float(np.max(_imbalance(out)))
    recovery = None
    if slo is not None and worst > slo:
        p = np.asarray(out.counts).shape[-1]
        n_local = (out.n + (-out.n) % p) // p
        ladder = []
        if out.indices is None and spec.tag is None:
            ladder.append(("tag", dataclasses.replace(spec, tag=True)))
        refine_base = (dataclasses.replace(spec, tag=True)
                       if out.indices is None and spec.tag is None else spec)
        ladder.append(("refine", _refined_spec(refine_base, p, n_local)))
        for name, cand_spec in ladder:
            try:
                cand = inner(cand_spec)
            except ValueError:   # tag packing budget does not fit
                continue
            rep = _finalize_audit(cand, cand_spec)
            if rep is not None and not rep.ok:
                raise VerificationError(
                    "imbalance-SLO recovery attempt failed its own audit: "
                    + rep.describe(), rep)
            ci = float(np.max(_imbalance(cand)))
            if ci <= slo:
                out, worst, recovery = cand, ci, name
                break
        else:
            _update_recovery(out, spec, achieved_imbalance=worst)
            raise ImbalanceError(
                f"achieved_imbalance {worst:.3f} > imbalance_slo {slo:.3f} "
                f"after duplicate tagging and bonus refinement "
                f"(algorithm={spec.algorithm}, eps={spec.eps})", worst, slo)
    if getattr(out, "audit", None) is not None:
        out.audit.achieved_imbalance = _imbalance(out)
    _update_recovery(out, spec, achieved_imbalance=worst,
                     imbalance_recovery=recovery)
    return out


def _with_policies(run, spec: SortSpec, *, batched: bool = False):
    """The full policy stack around one sort: the overflow policy runs
    innermost (every launch, including verify/SLO re-launches, gets
    overflow recovery), then the verification policy, then the
    imbalance SLO."""
    inner = lambda s: _with_overflow_policy(run, s)
    out = inner(spec)
    out = _enforce_verify(inner, spec, out, batched=batched)
    out = _enforce_slo(inner, spec, out, batched=batched)
    return out


def sort(x, spec: SortSpec | None = None, **overrides) -> SortOutput:
    """Sort a 1-D array of keys across the mesh. Returns a SortOutput whose
    `shards`/`counts` are the distributed result and `.gather()` the flat
    sorted array. Float keys and duplicate-heavy keys are handled by the
    adapter layer automatically; see SortSpec for every knob — including
    `on_overflow`, the capacity-overflow recovery policy (raise | retry |
    spill; DESIGN.md Section 8). With `SortSpec(batch=True)` a (B, n)
    array routes through the batched single-launch engine (see
    `sort_batched`)."""
    spec = _as_spec(spec, overrides)
    if spec.batch:
        return sort_batched(x, spec)
    with spans.span(spans.FRONT_DOOR):
        return _with_policies(
            lambda s: _sort_impl(x, s, want_indices=False), spec)


def sort_batched(xs, spec: SortSpec | None = None, **overrides):
    """Sort B independent key arrays in ONE shard_map launch.

    xs: a (B, n) array (or anything stackable to one) of B equal-length
    requests — returns a BatchedSortOutput — or a list/tuple of 1-D arrays
    of arbitrary lengths, which is length-bucketed (one batched launch per
    distinct length; `launch.serve.serve_bucketed`-style near-length
    bucketing upstream maximizes sharing) and returns a list of per-request
    SortOutputs in input order.

    Per request the result is bit-identical to `sort()` on that request
    with the same spec/seed, but a batch of B costs one launch, one
    all_gather + one psum per splitter round, and (dense strategy) one
    all_to_all — independent of B — plus a compiled-executable cache hit
    for every shape bucket already seen (DESIGN.md Section 6).
    """
    spec = _as_spec(spec, overrides)
    with spans.span(spans.FRONT_DOOR):
        if isinstance(xs, (list, tuple)):
            return _sort_batched_buckets(xs, spec)
        return _with_policies(
            lambda s: _sort_batched_impl(jnp.asarray(xs), s,
                                         want_indices=False),
            spec, batched=True)


def gather_perm_checked(out: "SortOutput", what: str) -> np.ndarray:
    """argsort/sort_kv exactness check, without a device sync: a truncated
    permutation is silent corruption, but dropped keys are exactly the
    keys missing from the gather — so verify the gathered LENGTH (counts
    are materialized by the gather anyway) instead of blocking on the
    device-side overflow counter. Strictly more precise, too: the counter
    also counts harmless sample-buffer overflow, which drops no keys."""
    order = out.gather_indices()
    if order.shape[0] != out.n:
        raise RuntimeError(
            f"{what}: exchange dropped {out.n - order.shape[0]} keys "
            "(capacity overflow) — the result would not be a permutation. "
            "Use on_overflow='retry'/'spill', raise pair_factor/out_slack, "
            "or use exchange='allgather'.")
    return order


def argsort(x, spec: SortSpec | None = None, **overrides) -> np.ndarray:
    """Stable distributed argsort: the permutation that sorts x, as a flat
    (n,) NumPy array. Implemented via implicit tagging — the per-key tag IS
    the original index, so the permutation falls out of the sorted keys.
    Raises if the exchange dropped keys (the result must be exact);
    `on_overflow="retry"`/"spill" recover instead of raising."""
    spec = dataclasses.replace(_as_spec(spec, overrides), stable=True)
    with spans.span(spans.FRONT_DOOR):
        out = _with_policies(
            lambda s: _sort_impl(x, s, want_indices=True), spec)
    return gather_perm_checked(out, "argsort")


def sort_kv(keys, values, spec: SortSpec | None = None, **overrides):
    """Sort (key, value) pairs by key, stably. Returns (sorted_keys,
    sorted_values) as NumPy arrays; values may be multi-dimensional (the
    permutation applies along axis 0)."""
    values = np.asarray(values)
    keys = jnp.asarray(keys)
    if values.shape[:1] != keys.shape:
        raise ValueError(f"values leading dim {values.shape[:1]} != "
                         f"keys shape {keys.shape}")
    spec = dataclasses.replace(_as_spec(spec, overrides), stable=True)
    with spans.span(spans.FRONT_DOOR):
        out = _with_policies(
            lambda s: _sort_impl(keys, s, want_indices=True), spec)
    order = gather_perm_checked(out, "sort_kv")
    return out.gather(), values[order]


def _record_spec_supported(spec: SortSpec) -> None:
    """What a record sort runs today: HSS, the dense exchange, overflow
    counted and not recovered, no audit."""
    if spec.verify != "off":
        raise NotImplementedError(
            f"sort_records: verify={spec.verify!r} — no audit checks "
            "records yet; use verify='off'")
    unsupported = {
        "algorithm": spec.algorithm != "hss",
        "exchange": spec.resolved_exchange() != "dense",
        "on_overflow": spec.on_overflow != "raise",
        "imbalance_slo": spec.imbalance_slo is not None,
        "batch": spec.batch,
        "local_sort_fn": spec.local_sort_fn is not None,
        "initial_probes": spec.initial_probes is not None,
    }
    bad = [k for k, v in unsupported.items() if v]
    if bad:
        raise NotImplementedError(
            f"sort_records supports algorithm='hss', exchange='dense', "
            f"on_overflow='raise' and nothing set of {bad}")


def _sort_records_impl(x, spec: SortSpec) -> RecordSortOutput:
    part = get_partitioner(spec.algorithm)
    if x.dtype == np.uint8 and x.ndim == 2 and x.shape[1] == RECORD_BYTES:
        x = pack_records(x)
    p, names, sizes = _mesh_axes(spec, part)
    with spans.span(spans.PLAN):
        plan = make_plan(x, spec, p, records=True)
    ctx = ShardCtx(spec=spec, axis_names=names, sizes=sizes, rng=None)
    raw = driver.run(
        lambda local, rng: part.sharded(local, rng, ctx),
        x, mesh=spec.mesh, axis_names=names, sizes=sizes, seed=spec.seed,
        n_real=plan.n, local_sort_fn=local_sort_records,
        cache_key=_cache_key(spec, names, sizes, x, kind="records"))
    with spans.span(spans.DECODE):
        return plan.decode(raw)


def sort_records(records, spec: SortSpec | None = None,
                 **overrides) -> RecordSortOutput:
    """Sort Sort Benchmark records (100 bytes: a 10-byte key, a 90-byte
    payload) across the mesh by memcmp order of their keys; records with
    equal keys come out in any order.

    `records` is their device form: (25, n) uint32 word planes, each word
    four bytes big-endian (`repro.sort.pack_records` makes them on the
    device from (n, 100) uint8), sharded over the mesh on the last axis;
    (n, 100) uint8 records are packed first. The payload moves with its
    key on the device; the result (`RecordSortOutput`) stays there until
    `gather()`. The call runs the chain `sort()` runs — plan,
    one cached `shard_map` launch of `HSSPartitioner.sharded`, decode —
    with no duplicate detection: HSS breaks ties between equal keys by
    each record's global index. Only HSS with the dense exchange, and no
    audit (`verify` other than "off" raises), for now."""
    spec = _as_spec(spec, overrides)
    _record_spec_supported(spec)
    with spans.span(spans.FRONT_DOOR):
        return _sort_records_impl(records, spec)


def gather(out: SortOutput) -> np.ndarray:
    """Module-level alias for SortOutput.gather()."""
    return out.gather()
