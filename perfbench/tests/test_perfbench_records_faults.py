"""A whole run of the record-sort system on host devices: `correct` holds
for the program, and comes out false when the timed path is broken
underneath, once for each fault a record sort across chips can have."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

import rehearsal
from harness import record_reference, traffic

RECORDS = "t.records"
CONFIG = {"system": "record_direct", "chips": 4, "records_per_chip": 2048,
          "spec": {"algorithm": "hss", "eps": 0.05}}
MIX = {"loop": "closed", "distribution": "gensort", "inputs": 2,
       "warmup_calls": 1, "check_calls": 2, "check_among_first": 3}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The rehearsal's checkout plus a record cell, its config and mix, and
    the real record metrics moved onto it."""
    root = rehearsal.make_root(tmp_path_factory.mktemp("bench"))
    perfbench = root / "perfbench"
    (perfbench / "configs" / "t_records.json").write_text(json.dumps(CONFIG))
    (perfbench / "traffic" / "t_gensort.json").write_text(json.dumps(MIX))
    real = json.loads((rehearsal.PERFBENCH.parent / "BENCHMARK.json")
                      .read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "t_records", "source": "test",
                             "reduced": [], "why": "test",
                             "file": "perfbench/configs/t_records.json"})
    bench["workloads"].append({"name": RECORDS, "config": "t_records",
                               "traffic": "t_gensort", "chips": 4,
                               "why": "test"})
    for section in ("end_to_end", "per_layer"):
        ours = {m["name"]: m for m in bench[section]}
        for m in real[section]:
            if any(w.startswith("hss_records.")
                   for w in m.get("workloads", ())):
                ours[m["name"]] = dict(
                    ours.get(m["name"], m),
                    workloads=ours.get(m["name"], m)["workloads"]
                    + [RECORDS])
        bench[section] = list(ours.values())
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_records(root, *, seed=3, trace=0):
    rehearsal.CHIPS.setdefault(RECORDS, 4)
    return rehearsal.run_cell(root, RECORDS, seed=seed, trace=trace)


@pytest.fixture
def shared_prefixes(monkeypatch):
    """gensort records whose keys share their first 8 bytes in a handful
    of values, so bytes 8 and 9 decide the order."""
    real = traffic._part

    def part(kind, name):
        module = real(kind, name)
        if kind == "keys" and name == "gensort":
            draw = module.draw

            def narrowed(key, n, mix):
                w = draw(key, n, mix)
                return w.at[0].set(w[0] & jnp.uint32(3)).at[1].set(0)
            module.draw = narrowed
        return module

    monkeypatch.setattr(traffic, "_part", part)


def _words(out):
    return np.array(out.shards), np.array(out.counts)


def _set(out, shards, counts):
    out.shards = jnp.asarray(shards)
    out.counts = jnp.asarray(counts, jnp.int32)


def _payload_in_place(out, x):     # keys sorted, each payload left behind
    shards, counts = _words(out)
    x = np.asarray(x)
    at = 0
    for s in range(shards.shape[0]):
        c = counts[s]
        shards[s, 3:, :c] = x[3:, at:at + c]
        shards[s, 2, :c] = (shards[s, 2, :c] & 0xFFFF0000) | (
            x[2, at:at + c] & 0xFFFF)
        at += c
    _set(out, shards, counts)


def _first_8_bytes(out, x):        # ordered by key bytes 0-7 alone
    shards, counts = _words(out)
    x = np.asarray(x)
    order = np.lexsort((x[1], x[0]))
    at = 0
    for s in range(shards.shape[0]):
        c = counts[s]
        shards[s, :, :c] = x[:, order[at:at + c]]
        at += c
    _set(out, shards, counts)


def _half_dropped(out, x):         # half of the shards' records dropped
    p = out.shards.shape[0]
    out.counts = out.counts.at[p // 2:].set(0)


def _no_exchange(out, x):          # each chip sorts only its own records
    shards, _ = _words(out)
    x = np.asarray(x)
    p = shards.shape[0]
    n_local = x.shape[1] // p
    for s in range(p):
        block = x[:, s * n_local:(s + 1) * n_local]
        shards[s, :, :n_local] = block[:, np.lexsort(block[2::-1])]
    _set(out, shards, [n_local] * p)


FAULTS = {"payload_left_in_place": _payload_in_place,
          "first_8_key_bytes_only": _first_8_bytes,
          "half_the_shards_dropped": _half_dropped,
          "exchange_left_out": _no_exchange}


def test_program_runs_correct(root):
    result = run_records(root)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["checks"]["mismatched_records"] == {"value": 0,
                                                      "limit": 0}
    assert result["metrics"]["sort_keys_per_s"]["value"] > 0
    assert set(result["metrics"]) == {"sort_keys_per_s", "setup_s"}


def test_program_runs_correct_on_shared_prefixes(root, shared_prefixes):
    result = run_records(root, seed=7)
    assert result["correct"] is True


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(root, monkeypatch,
                                          shared_prefixes, fault):
    import repro.sort

    real = repro.sort.sort_records

    def broken(x, spec=None, **kw):
        out = real(x, spec, **kw)
        FAULTS[fault](out, x)
        return out

    monkeypatch.setattr(repro.sort, "sort_records", broken)
    result = run_records(root, seed=4)
    assert result["correct"] is False
    assert result["checks"]["mismatched_records"]["value"] > 0


def test_reference_reads_words_as_the_program_packs_them():
    from repro.sort import pack_records
    recs = np.random.default_rng(0).integers(0, 256, (50, 100), np.uint8)
    assert np.array_equal(
        record_reference.records_from_words(pack_records(recs)), recs)


def test_traced_run_reports_the_record_metrics(root):
    result = run_records(root, seed=6, trace=1)
    assert result["correct"] is True
    names = json.loads((rehearsal.PERFBENCH.parent / "BENCHMARK.json")
                       .read_text())["per_layer"]
    want = {m["name"] for m in names
            if "hss_records.gensort_p4" in m.get("workloads", ())}
    assert {"front_door_ms.records", "splitter_rounds.records",
            "exchange_fill.records", "device_idle.records"} <= set(
        result["metrics"]) <= want
    fill = result["metrics"]["exchange_fill.records"]["value"]
    assert 0 < fill <= 100
    assert result["metrics"]["splitter_rounds.records"]["value"] >= 1


def test_parent_without_sort_records_fails_at_once(root, monkeypatch):
    import repro.sort
    monkeypatch.delattr(repro.sort, "sort_records")
    with pytest.raises(SystemExit, match="sort_records"):
        run_records(root, seed=8)
