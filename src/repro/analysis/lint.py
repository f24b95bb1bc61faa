"""The static-analysis lint CLI: `python -m repro.analysis.lint`.

Sweeps the shipped program matrix — five partitioners x exchange
strategies x single/batched x kernel policies, plus the top-k program —
and proves every registered CommsContract over the traced jaxprs, runs
the host-sync / retrace purity audits, and evaluates the Pallas VMEM
budgets. Emits a machine-readable ANALYSIS.json and exits nonzero on any
violation; CI runs it as a blocking step, so a collective-structure
regression (an extra all_to_all, a B-dependent psum, a host sync on the
launch path, an oversized kernel block) fails the build before any
benchmark notices.

Flags:
  --out PATH      where to write ANALYSIS.json (default: repo cwd)
  --skip-purity   trace-only mode: skip the execution-based purity audits
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

REQUIRED_DEVICES = 8


def _use_host_devices() -> None:
    """The lint is a CPU-only tool: it traces (and, for the purity audits,
    runs) the program matrix on REQUIRED_DEVICES simulated host devices.
    Pin the CPU platform and the device count before the backend starts,
    in this process — an accelerator on the machine stays untouched."""
    import jax
    flag = f"--xla_force_host_platform_device_count={REQUIRED_DEVICES}"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    jax.config.update("jax_platforms", "cpu")
    if jax.device_count() < REQUIRED_DEVICES:
        print(f"repro.analysis.lint: {REQUIRED_DEVICES} host devices "
              f"required, have {jax.device_count()} (the backend started "
              "before the lint could size it)", file=sys.stderr)
        sys.exit(2)


ALGOS = ("hss", "sample_random", "sample_regular", "ams")
P, N_LOCAL = 8, 128
BATCHES = (1, 8)


def _merge_counts(*dicts):
    out = {}
    for d in dicts:
        for k, v in d.items():
            out[k] = out.get(k, 0) + v
    return out


def _record(results, section, name, ok, detail=""):
    results["checks"].append(
        {"section": section, "name": name, "ok": bool(ok), "detail": detail})
    status = "ok" if ok else "FAIL"
    print(f"  [{status:4s}] {section:9s} {name}" + (f"  {detail}" if not ok
                                                    else ""))
    if not ok:
        results["ok"] = False


def _check(results, section, name, report):
    detail = "; ".join(str(v) for v in report.violations)
    _record(results, section, name, report.ok, detail)


def run_contracts(results) -> None:
    import jax

    from repro.analysis import comms, contracts
    from repro.analysis.contracts import CommsContract
    from repro.analysis.programs import (
        make_topk_program, partitioner_program, splitters_program)
    from repro.core.exchange import EXCHANGE_COLLECTIVES
    from repro.sort.partitioners import MULTISTAGE_BASE_COLLECTIVES

    exchanges = tuple(EXCHANGE_COLLECTIVES)

    print("contracts: splitter phase")
    for algo in ALGOS:
        contract = contracts.get_contract(f"splitters:{algo}")
        fn, args = splitters_program(algo, p=P, n_local=N_LOCAL)
        _check(results, "contracts", f"splitters:{algo}",
               contracts.check_program(fn, args, contract))
        _check(results, "contracts", f"splitters:{algo}[batch]",
               contracts.check_batch_invariance(
                   lambda b, a=algo: splitters_program(a, batch=b, p=P,
                                                       n_local=N_LOCAL),
                   contract, batches=BATCHES))

    print("contracts: full pipeline (splitters + exchange)")
    reports = []
    for algo in ALGOS:
        base = contracts.get_contract(f"splitters:{algo}")
        for exchange in exchanges:
            expect = _merge_counts(base.total_counts,
                                   EXCHANGE_COLLECTIVES[exchange])
            full = CommsContract(
                name=f"{algo}+{exchange}",
                total_counts=expect,
                forbid=("ppermute",),
                round_collectives=base.round_collectives,
                converged_branch_pure=base.converged_branch_pure)
            fn, args = partitioner_program(algo, exchange=exchange,
                                           p=P, n_local=N_LOCAL)
            jx = jax.make_jaxpr(fn)(*args)
            _check(results, "contracts", f"{algo}+{exchange}",
                   contracts.check_jaxpr(jx, full))
            reports.append(comms.analyze_jaxpr(
                jx, label=f"{algo}+{exchange}").to_json())
            _check(results, "contracts", f"{algo}+{exchange}[batch]",
                   contracts.check_batch_invariance(
                       lambda b, a=algo, e=exchange: partitioner_program(
                           a, exchange=e, batch=b, p=P, n_local=N_LOCAL),
                       full, batches=BATCHES))

    print("contracts: multistage (base + 2 exchanges)")
    for exchange in exchanges:
        expect = _merge_counts(
            MULTISTAGE_BASE_COLLECTIVES,
            {k: 2 * v for k, v in EXCHANGE_COLLECTIVES[exchange].items()})
        full = CommsContract(name=f"multistage+{exchange}",
                             total_counts=expect, forbid=("ppermute",))
        fn, args = partitioner_program("multistage", exchange=exchange,
                                       p=P, n_local=N_LOCAL)
        jx = jax.make_jaxpr(fn)(*args)
        _check(results, "contracts", f"multistage+{exchange}",
               contracts.check_jaxpr(jx, full))
        reports.append(comms.analyze_jaxpr(
            jx, label=f"multistage+{exchange}").to_json())
        _check(results, "contracts", f"multistage+{exchange}[batch]",
               contracts.check_batch_invariance(
                   lambda b, e=exchange: partitioner_program(
                       "multistage", exchange=e, batch=b, p=P,
                       n_local=N_LOCAL),
                   full, batches=BATCHES))

    print("contracts: kernel-policy independence (hss+dense)")
    from repro.sort.spec import SortSpec
    base = contracts.get_contract("splitters:hss")
    full = CommsContract(
        name="hss+dense", forbid=("ppermute",),
        total_counts=_merge_counts(base.total_counts,
                                   EXCHANGE_COLLECTIVES["dense"]),
        round_collectives=base.round_collectives,
        converged_branch_pure=True)
    for policy in ("auto", "pallas", "xla"):
        fn, args = partitioner_program(
            "hss", exchange="dense", p=P, n_local=N_LOCAL,
            spec=SortSpec(algorithm="hss", exchange="dense",
                          kernel_policy=policy))
        _check(results, "contracts", f"hss+dense[kernel={policy}]",
               contracts.check_program(fn, args, full))

    print("contracts: top_k")
    topk = contracts.get_contract("top_k")
    for batch in (None, 4):
        prog, args, c = make_topk_program(k=10, batch=batch, p=P,
                                          n_local=N_LOCAL)
        pinned = dataclasses.replace(topk, gather_widths=(c,))
        tag = "single" if batch is None else f"B={batch}"
        _check(results, "contracts", f"top_k[{tag}]",
               contracts.check_program(prog, args, pinned))
    _check(results, "contracts", "top_k[batch]",
           contracts.check_batch_invariance(
               lambda b: make_topk_program(k=10, batch=b, p=P,
                                           n_local=N_LOCAL)[:2],
               topk, batches=BATCHES))

    results["comms_reports"] = reports


def run_vmem(results) -> None:
    from repro.analysis import vmem

    print("vmem: kernel budgets")
    try:
        checked = vmem.check_kernel_budgets(platform="tpu", p=256,
                                            itemsizes=(4, 8))
    except vmem.VmemBudgetError as e:
        _record(results, "vmem", "kernel_budgets", False, str(e))
        return
    for fp in checked:
        _record(results, "vmem", f"{fp.family}[{fp.config}]", True)
    results["vmem_footprints"] = [fp.to_json() for fp in checked]


def run_purity(results) -> None:
    import numpy as np

    import jax.numpy as jnp

    from repro.analysis import purity
    from repro.analysis.programs import partitioner_program
    from repro.sort.api import sort, sort_batched
    from repro.sort.semisort import semisort, top_k
    from repro.sort.spec import SortSpec

    rng = np.random.default_rng(0)

    print("purity: launch path is device->host sync free")
    import jax
    for algo in ("hss", "ams"):
        fn, abstract_args = partitioner_program(algo, exchange="dense", p=P,
                                                n_local=N_LOCAL)
        # structural proof, backend-independent: the program traces with
        # abstract inputs, so nothing on its data path can concretize
        try:
            purity.assert_sync_free_trace(fn, *abstract_args)
            ok, detail = True, ""
        except purity.HostSyncViolation as e:
            ok, detail = False, str(e)
        _record(results, "purity", f"launch:{algo}+dense[static]", ok, detail)
        if not purity.transfer_guard_effective():
            continue   # guard is a no-op on host-resident (cpu) buffers
        data = jnp.asarray(
            rng.permutation(P * N_LOCAL).astype(np.int32).reshape(P, N_LOCAL))
        key = jax.random.key(0)
        jitted = jax.jit(fn)
        try:
            out = purity.assert_no_host_sync(
                lambda: jax.block_until_ready(jitted(data, key)))
            ok, detail = out is not None, ""
        except purity.HostSyncViolation as e:
            ok, detail = False, str(e)
        _record(results, "purity", f"launch:{algo}+dense[guard]", ok, detail)

    print("purity: warm front doors never retrace")
    spec = SortSpec(exchange="allgather", tag=False)
    n = P * 131   # a shape bucket the test-suite does not use
    audits = {
        "sort": lambda: sort(
            jnp.asarray(rng.permutation(n).astype(np.int32)), spec),
        "sort_batched": lambda: sort_batched(
            jnp.asarray(np.stack([rng.permutation(n).astype(np.int32)
                                  for _ in range(2)])), spec),
        "semisort": lambda: semisort(
            jnp.asarray(rng.integers(0, 50, size=n).astype(np.int32))),
        "top_k": lambda: top_k(
            jnp.asarray(rng.permutation(n).astype(np.int32)), 10),
    }
    for name, call in audits.items():
        try:
            purity.audit_retrace(call)
            ok, detail = True, ""
        except purity.RetraceViolation as e:
            ok, detail = False, str(e)
        _record(results, "purity", f"retrace:{name}", ok, detail)

    print("purity: semisort heavy stats materialize lazily")
    out = semisort(jnp.asarray(rng.integers(0, 50, size=n).astype(np.int32)))
    deferred = getattr(out, "_decode", None) is not None
    _record(results, "purity", "semisort:deferred_heavy_stats", deferred,
            "" if deferred else "front door materialized heavy stats "
            "eagerly (host-blocking sync on the serving hot path)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro.analysis.lint",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--out", default="ANALYSIS.json")
    ap.add_argument("--skip-purity", action="store_true",
                    help="trace-only: skip execution-based purity audits")
    args = ap.parse_args(argv)

    _use_host_devices()

    import jax

    results = {
        "schema": 1,
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "matrix": {"p": P, "n_local": N_LOCAL, "batches": list(BATCHES)},
        "ok": True,
        "checks": [],
    }
    run_contracts(results)
    run_vmem(results)
    if args.skip_purity:
        print("purity: skipped (--skip-purity)")
    else:
        run_purity(results)

    n_fail = sum(1 for c in results["checks"] if not c["ok"])
    results["failures"] = n_fail
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"{len(results['checks'])} checks, {n_fail} failure(s) "
          f"-> {args.out}")
    return 0 if results["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
