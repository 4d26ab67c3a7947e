"""The declarative experiment API (ISSUE 3 tentpole): specs, registries,
run store, Session, CLI.

The heavier guarantees pinned here:

* spec content hashes are STABLE across processes and releases (hardcoded
  hex — a drift silently orphans every stored run);
* a policy registered via ``register_policy`` sweeps through the vmapped
  ``run_batch`` path with correct counters, without touching
  ``src/repro/uvm/simulator.py``;
* the raw ``run_ours`` path returns bit-identical counters to ``Session``
  (and the retired ``Ctx`` shim stays gone).
"""
import json
import os

import pytest

from repro.configs.predictor_paper import SMOKE
from repro.uvm import registry as REG
from repro.uvm import simulator as S
from repro.uvm import trace as T
from repro.uvm.api import (
    CellSpec,
    ExperimentSpec,
    ModelSpec,
    PolicySpec,
    PrefetchSpec,
    RunStore,
    Session,
    WorkloadSpec,
    register_policy,
    register_prefetcher,
    register_predictor,
)
from repro.uvm.api.specs import PretrainSpec, ProtocolSpec, TrainSpec, spec_from_dict


def _quick_session(tmp_path, **kw) -> Session:
    kw.setdefault("store", RunStore(tmp_path / "runs"))
    return Session(**kw)


# --- specs -------------------------------------------------------------------


def test_spec_json_roundtrip():
    cell = CellSpec(
        WorkloadSpec("ATAX", 0.3, 1500), "sim", PolicySpec("hpe"), PrefetchSpec("demand"), 1.5
    )
    back = CellSpec.from_json(cell.to_json())
    assert back == cell and back.key == cell.key

    exp = ExperimentSpec(
        name="x",
        workloads=(WorkloadSpec("NW"), WorkloadSpec.concurrent(("ATAX", "BICG"), slice_len=512)),
        policies=(PolicySpec("lru"), PolicySpec("belady")),
        prefetchers=(PrefetchSpec("tree"),),
        oversubscriptions=(1.25, 1.5),
    )
    back = ExperimentSpec.from_json(exp.to_json())
    assert back == exp and back.key == exp.key
    assert len(exp.cells()) == 2 * 2 * 1 * 2

    ours = CellSpec(
        WorkloadSpec("Hotspot"), "ours", PolicySpec("learned"), PrefetchSpec("none"),
        model=ModelSpec(predictor=SMOKE, train=TrainSpec(), pretrain=PretrainSpec(seed0=123)),
    )
    assert CellSpec.from_json(ours.to_json()) == ours

    proto = ProtocolSpec(WorkloadSpec("NW"), "ours", ModelSpec(pretrain=PretrainSpec()), prior=("abc",))
    assert ProtocolSpec.from_json(proto.to_json()) == proto
    # generic reconstruction (what `cli report` relies on)
    assert spec_from_dict("CellSpec", cell.to_dict()) == cell


def test_spec_content_hash_stability():
    """Pinned hex: a hash-scheme change orphans every stored run — bump
    specs.SCHEMA intentionally instead, and regenerate these constants.
    (Regenerated for SCHEMA 2: PR 5's mux tenancy changed what a
    concurrent `ours` result means; regenerated again when PR 7 grew
    `WorkloadSpec.drift`, which moves every workload hash; regenerated
    for SCHEMA 3 when PR 9 grew `ModelSpec.qos` capacity partitioning.)"""
    assert WorkloadSpec("ATAX").key == "7363c55d1784e19f"
    assert CellSpec(WorkloadSpec("ATAX")).key == "d9894afe33c1a780"
    # any field change moves the key
    keys = {
        CellSpec(WorkloadSpec("ATAX")).key,
        CellSpec(WorkloadSpec("ATAX", scale=0.5)).key,
        CellSpec(WorkloadSpec("ATAX"), policy=PolicySpec("hpe")).key,
        CellSpec(WorkloadSpec("ATAX"), oversubscription=1.5).key,
        CellSpec(WorkloadSpec("ATAX"), strategy="uvmsmart").key,
        CellSpec(WorkloadSpec.drifting(("StreamTriad", "PtrChase"))).key,
    }
    assert len(keys) == 6


def test_cellspec_validation():
    with pytest.raises(ValueError):
        CellSpec(WorkloadSpec("ATAX"), "bogus")
    with pytest.raises(ValueError):
        CellSpec(WorkloadSpec("ATAX"), "ours")  # no model


# --- registries --------------------------------------------------------------


def test_registry_duplicate_name_rejected():
    with pytest.raises(ValueError):
        register_policy("lru", lambda st, i, t: (st.last_access,))
    with pytest.raises(ValueError):
        register_prefetcher("tree", lambda r, b, v, n: r)
    with pytest.raises(ValueError):
        register_predictor("transformer", lambda cfg: None)
    with REG.scoped():
        register_policy("tmp_policy", lambda st, i, t: (st.last_access,))
        with pytest.raises(ValueError):
            register_policy("tmp_policy", lambda st, i, t: (st.last_access,))
    assert "tmp_policy" not in REG.policy_names()  # scoped() restored


def test_builtin_ids_stable():
    assert S.POLICY_IDS == {"lru": 0, "random": 1, "belady": 2, "hpe": 3, "learned": 4}
    assert S.PREFETCH_IDS == {"demand": 0, "tree": 1, "none": 0}
    assert set(REG.predictor_names()) >= {"transformer", "lstm", "cnn", "mlp"}


def test_registered_policy_rides_run_batch():
    """A ~5-line custom policy sweeps through the vmapped run_batch path —
    no simulator.py edits: a builtin-clone must be bit-identical to the
    builtin in the SAME sweep, and an actually-different policy must match
    its own single-cell run."""
    tr = T.get_trace("ATAX", scale=0.25).slice(0, 1500)
    with REG.scoped():
        register_policy("lru_clone", lambda st, i, t: (st.last_access,))
        register_policy("mru", lambda st, i, t: (-st.last_access,))
        out = S.run_batch(tr, [
            ("lru", "tree", 1.25), ("lru_clone", "tree", 1.25),
            ("mru", "tree", 1.25), ("mru", "demand", 1.5),
        ])
        assert out[0] == out[1]
        assert out[2] != out[0]
        for cell, got in zip([("mru", "tree", 1.25), ("mru", "demand", 1.5)], out[2:]):
            want = S.run(tr, policy=cell[0], prefetch=cell[1], oversubscription=cell[2]).stats
            assert got == want, cell
    # builtins unaffected after the scope ends
    assert S.run_batch(tr, [("lru", "tree", 1.25)])[0] == S.run(tr, policy="lru", prefetch="tree").stats


def test_registered_prefetcher_rides_run_batch():
    """A registered prefetcher (here: a clone of the builtin tree mask)
    dispatches through the same traced branch table."""
    from repro.uvm.simulator import _tree_mask

    tr = T.get_trace("Hotspot", scale=0.25).slice(0, 1500)
    with REG.scoped():
        register_prefetcher("tree_clone", _tree_mask)
        out = S.run_batch(tr, [("lru", "tree", 1.25), ("lru", "tree_clone", 1.25)])
        assert out[0] == out[1]


def test_scoped_registration_never_leaves_stale_jits():
    """Version numbers are monotonic across scoped() rollbacks: a scan
    compiled INSIDE a scope must never be served to a later registration
    that happens to land on the same version number (it would silently run
    the wrong branch table — lru2 below would clamp onto `learned`)."""
    from repro.uvm.simulator import _tree_mask

    tr = T.get_trace("ATAX", scale=0.25).slice(0, 1500)
    want = S.run_batch(tr, [("lru", "tree", 1.25)])[0]
    with REG.scoped():
        register_prefetcher("tree2", _tree_mask)
        S.run_batch(tr, [("lru", "tree2", 1.25)])  # compiles at the scope's version
    with REG.scoped():
        register_policy("lru2", lambda st, i, t: (st.last_access,))
        assert S.run_batch(tr, [("lru2", "tree", 1.25)])[0] == want


def test_registered_policy_via_session(tmp_path):
    """The Session/CellSpec path accepts registered policies, but never
    PERSISTS their cells: a spec hashes a plugin by name only, so a changed
    implementation under the same name must not be served stale results."""
    with REG.scoped():
        register_policy("mru2", lambda st, i, t: (-st.last_access,))
        s = _quick_session(tmp_path, scale=0.25, cap=1500)
        got = s.run(CellSpec(s.workload("ATAX"), "sim", PolicySpec("mru2"), PrefetchSpec("tree"), 1.25))
        want = S.run(s.trace("ATAX"), policy="mru2", prefetch="tree").stats
        assert got == want
        assert list(s.store.records()) == []  # plugin cells stay in-process only


# --- run store ---------------------------------------------------------------


def test_run_store_roundtrip_and_corruption(tmp_path):
    store = RunStore(tmp_path / "runs")
    spec = CellSpec(WorkloadSpec("ATAX"))
    assert store.get(spec) is None
    p = store.put(spec, {"pages_thrashed": 7})
    assert p is not None and store.get(spec) == {"pages_thrashed": 7}
    assert store.hits == 1 and store.misses == 1 and store.writes == 1
    for garbage in ("{torn", "[1, 2]", '"not a record"'):  # all read as misses
        p.write_text(garbage)
        assert store.get(spec) is None
        assert [k for k, _ in RunStore(tmp_path / "runs").records()] == []


def test_run_store_killed_writer_leaves_no_damage(tmp_path):
    """A writer killed mid-publish leaves only a `.tmp.<pid>` turd: the
    published record (if any) still reads back, the turd is invisible to
    get()/records(), and a later publish succeeds over it."""
    store = RunStore(tmp_path / "runs")
    spec = CellSpec(WorkloadSpec("ATAX"))
    p = store.put(spec, {"faults": 1})
    # simulate a crash between tmp-write and os.replace: a half-written
    # tmp file sits next to the (old) published record
    turd = p.with_suffix(".tmp.99999")
    turd.write_text('{"schema": 2, "key": "' + spec.key + '", "result": {"faults":')
    assert store.get(spec) == {"faults": 1}
    assert [k for k, _ in store.records()] == [spec.key]
    # republish over the turd: atomic replace still lands the new record
    assert store.put(spec, {"faults": 2}) == p
    assert store.get(spec) == {"faults": 2}
    assert turd.exists()  # turds are inert, never silently adopted


def test_run_store_torn_record_reads_as_miss_then_heals(tmp_path):
    """A torn published file (crash mid-sector, disk-full truncation) must
    read as a miss everywhere, and re-running the cell heals it."""
    store = RunStore(tmp_path / "runs")
    spec = CellSpec(WorkloadSpec("ATAX"))
    p = store.put(spec, {"faults": 3})
    whole = p.read_text()
    for cut in (1, len(whole) // 2, len(whole) - 2):  # torn at any offset
        p.write_text(whole[:cut])
        assert store.get(spec) is None
        assert [k for k, _ in store.records()] == []
    # wrong-key aliasing (a renamed file) is also rejected, not served
    other = CellSpec(WorkloadSpec("BICG"))
    store.put(other, {"faults": 9})
    os.replace(store.path(other.key), p)
    assert store.get(spec) is None
    # the heal: republishing restores a byte-identical good record
    assert store.put(spec, {"faults": 3}) == p
    assert p.read_text() == whole and store.get(spec) == {"faults": 3}


def test_run_store_disabled(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_RUN_STORE", "0")
    store = RunStore(tmp_path / "runs")
    spec = CellSpec(WorkloadSpec("ATAX"))
    assert store.put(spec, {"x": 1}) is None and store.get(spec) is None
    assert not (tmp_path / "runs").exists()


def test_sweep_served_from_store_across_sessions(tmp_path):
    exp = ExperimentSpec(
        workloads=(WorkloadSpec("ATAX", 0.25, 1500),),
        policies=(PolicySpec("lru"), PolicySpec("hpe")),
        prefetchers=(PrefetchSpec("demand"), PrefetchSpec("tree")),
        oversubscriptions=(1.25,),
    )
    s1 = _quick_session(tmp_path)
    r1 = s1.sweep(exp)
    assert s1.counters["computed"] == 4
    s2 = _quick_session(tmp_path)  # fresh process-equivalent: memory cold
    r2 = s2.sweep(exp)
    assert r2 == r1
    assert s2.counters == {"memory_hits": 0, "store_hits": 4, "computed": 0}


def test_random_policy_not_persisted(tmp_path):
    """`random` counters depend on lane padding (documented contract) — the
    store must never serve one sweep's random cell to a different sweep."""
    s = _quick_session(tmp_path, scale=0.25, cap=1500)
    s.sims("ATAX", [("random", "demand", 1.25), ("lru", "demand", 1.25)])
    kinds = [rec["spec"]["policy"]["name"] for _, rec in s.store.records()]
    assert "lru" in kinds and "random" not in kinds


# --- Session vs the deprecated entry points ---------------------------------


def test_session_sim_bit_identical_to_run(tmp_path):
    s = _quick_session(tmp_path, scale=0.25, cap=1500)
    for pol, pf, os_ in [("lru", "tree", 1.25), ("hpe", "demand", 1.5), ("belady", "demand", 1.25)]:
        want = S.run(s.trace("NW"), policy=pol, prefetch=pf, oversubscription=os_).stats
        assert s.sim("NW", pol, pf, os_) == want


def test_ctx_shim_is_gone():
    """The deprecated Ctx alias completed its removal schedule: importing
    it must fail, while benchmarks.common's surviving re-exports stay."""
    from benchmarks import common

    assert not hasattr(common, "Ctx")
    with pytest.raises(ImportError):
        from repro.uvm.api.session import Ctx  # noqa: F401
    # the moved quick-config survives under its old name ONE more PR, but
    # now warns (in-tree call sites migrated to CONFIG_QUICK in PR 10;
    # removal schedule in docs/API.md)
    from repro.configs.predictor_paper import CONFIG, CONFIG_QUICK

    with pytest.warns(DeprecationWarning, match="PCFG_QUICK is deprecated"):
        assert common.PCFG_QUICK is CONFIG_QUICK
    with pytest.warns(DeprecationWarning, match="PCFG_FULL is deprecated"):
        assert common.PCFG_FULL is CONFIG


def test_session_ours_bit_identical_to_run_ours(tmp_path, monkeypatch):
    """Session's learned cells reproduce raw run_ours exactly (counters AND
    accuracy), and a second session serves them from the store."""
    from repro.uvm import runtime as R

    monkeypatch.setattr(R, "PRETRAIN_CACHE_DIR", tmp_path / "cache")  # keep repo cache clean
    tr_name = "Hotspot"
    s = _quick_session(tmp_path, scale=0.3, cap=3000,
                       model=ModelSpec(predictor=SMOKE, train=TrainSpec()))
    res = s.ours(tr_name)
    want = R.run_ours(
        s.trace(tr_name), SMOKE, s.tcfg,
        oversubscription=1.25, table=s.pretrained(s.default_pretrain),
    )
    assert res.stats == want.stats
    assert res.top1 == want.top1 and res.n_predictions == want.n_predictions

    s2 = _quick_session(tmp_path, scale=0.3, cap=3000,
                        model=ModelSpec(predictor=SMOKE, train=TrainSpec()))
    res2 = s2.ours(tr_name)
    assert s2.counters["store_hits"] == 1 and s2.counters["computed"] == 0
    assert res2.stats == res.stats and res2.top1 == res.top1
    assert res2.per_group_acc == res.per_group_acc


def test_session_uvmsmart_matches_direct(tmp_path):
    from repro.uvm.uvmsmart import run_uvmsmart

    s = _quick_session(tmp_path, scale=0.25, cap=1500)
    assert s.uvmsmart("ATAX") == run_uvmsmart(s.trace("ATAX"), oversubscription=1.25)


def test_protocol_chain_cached_link_by_link(tmp_path, monkeypatch):
    """fig11's shape: links share one fine-tuned table, so link specs carry
    the chain prefix and a full rerun is served entirely from the store."""
    from repro.uvm import runtime as R

    monkeypatch.setattr(R, "PRETRAIN_CACHE_DIR", tmp_path / "cache")  # keep repo cache clean
    model = ModelSpec(predictor=SMOKE, train=TrainSpec())
    s = _quick_session(tmp_path, scale=0.25, cap=1500, model=model)
    pre = PretrainSpec(scale=0.15, seed0=123, benchmarks=("ATAX", "Hotspot"))
    chain = s.protocol_chain(["ATAX", "Hotspot"], "ours", pretrain=pre)
    assert len(chain) == 2 and all(r.n_samples > 0 for r in chain)

    s2 = _quick_session(tmp_path, scale=0.25, cap=1500, model=model)
    chain2 = s2.protocol_chain(["ATAX", "Hotspot"], "ours", pretrain=pre)
    assert s2.counters["computed"] == 0
    assert [r.top1 for r in chain2] == [r.top1 for r in chain]
    # a different prefix is a different spec: reordering misses the store
    s3 = _quick_session(tmp_path, scale=0.25, cap=1500, model=model)
    s3.protocol_chain(["Hotspot", "ATAX"], "ours", pretrain=pre)
    assert s3.counters["computed"] == 2


# --- CLI ---------------------------------------------------------------------


def test_cli_sweep_cache_hit_roundtrip(tmp_path, capsys):
    from repro.uvm import cli

    argv = ["sweep", "--benchmarks", "ATAX", "--policies", "lru", "--prefetchers",
            "demand", "tree", "--oversubs", "1.25", "--runs-dir", str(tmp_path / "runs"),
            "--scale", "0.25", "--cap", "1500"]
    assert cli.main(argv) == 0
    out1 = capsys.readouterr().out
    assert "hits=0 computed=2" in out1
    assert cli.main(argv) == 0
    out2 = capsys.readouterr().out
    assert "hits=2 computed=0" in out2
    # identical result lines (the cell rows, ignoring the counters line)
    rows = lambda s: [l for l in s.splitlines() if "thrash=" in l]
    assert rows(out1) == rows(out2)

    assert cli.main(["report", "--runs-dir", str(tmp_path / "runs")]) == 0
    rep = capsys.readouterr().out
    assert "2 stored runs" in rep and "ATAX" in rep


def test_cli_spec_dump_and_replay(tmp_path, capsys):
    from repro.uvm import cli

    spec_path = tmp_path / "exp.json"
    argv = ["sweep", "--benchmarks", "ATAX", "--policies", "lru", "--oversubs", "1.25",
            "--runs-dir", str(tmp_path / "runs"), "--scale", "0.25", "--cap", "1500",
            "--dump-spec", str(spec_path)]
    assert cli.main(argv) == 0
    capsys.readouterr()
    assert ExperimentSpec.from_json(spec_path.read_text()).cells()
    assert cli.main(["sweep", "--spec", str(spec_path), "--runs-dir", str(tmp_path / "runs")]) == 0
    assert "computed=0" in capsys.readouterr().out


def test_cli_run_single_cell(tmp_path, capsys):
    from repro.uvm import cli

    assert cli.main(["run", "--benchmark", "ATAX", "--policy", "belady", "--prefetch", "demand",
                     "--scale", "0.25", "--cap", "1500", "--runs-dir", str(tmp_path / "runs")]) == 0
    out = capsys.readouterr().out
    want = S.run(T.get_trace("ATAX", scale=0.25).slice(0, 1500), policy="belady", prefetch="demand").stats
    assert f"thrash={want['pages_thrashed']}" in out


def test_cli_run_and_sweep_share_store_keys(tmp_path, capsys):
    """`run` must hash a cell identically to `sweep`/Session for EVERY
    strategy (non-sim strategies canonicalise policy/prefetch) — otherwise
    the same logical run is recomputed and stored twice."""
    from repro.uvm import cli

    common = ["--scale", "0.25", "--cap", "1500", "--runs-dir", str(tmp_path / "runs")]
    assert cli.main(["sweep", "--benchmarks", "ATAX", "--strategy", "uvmsmart"] + common) == 0
    assert "computed=1" in capsys.readouterr().out
    assert cli.main(["run", "--benchmark", "ATAX", "--strategy", "uvmsmart"] + common) == 0
    assert "hits=1 computed=0" in capsys.readouterr().out


@pytest.mark.parametrize("from_env", [False, True], ids=["checkout-default", "env-var"])
def test_compile_cache_directory(tmp_path, from_env):
    """Importing the API turns on the persistent compile cache: in
    JAX_COMPILATION_CACHE_DIR when that is set (and compiled entries land
    there), else in the fixed directory inside the checkout."""
    import subprocess
    import sys
    from pathlib import Path

    from repro.util import CHECKOUT_CACHE_DIR

    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    want = str(CHECKOUT_CACHE_DIR)
    code = "import repro.uvm.api, jax; print(jax.config.jax_compilation_cache_dir)"
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
        code += ("\nfrom repro.uvm import simulator as S, trace as T"
                 "\nS.run_batch(T.get_trace('ATAX', scale=0.1), [('lru', 'tree', 1.25)])")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[0] == want
    if from_env:
        assert any((tmp_path / "cache").iterdir())
