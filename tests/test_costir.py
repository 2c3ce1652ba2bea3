"""Cost IR, planner-aware tuning, and the design-space explorer
(DESIGN.md 12).

The golden suite pins the PRE-refactor scalar builders' DesignReport numbers
(captured from the seed code, hex-exact floats) for pendigits-structure
networks across every (arch, style) combo — the array cost-IR builders must
reproduce them bit for bit, and the scalar reference engine must still equal
them too.  The tuning tests assert the planner-aware engine's contracts:
serial/batched decision parity, per-accept priced-cost monotonicity, and
never-worse-than-the-tnzd-engine priced cost.  The explorer tests assert the
Pareto dominance invariants.
"""
import numpy as np
import pytest

from repro.core.archs import ARCH_STYLES, design_cost
from repro.core.csd import bit_length_array, tnzd
from repro.core.hwmodel import CostSheet, adder, adder_vec, multiplier, \
    multiplier_vec, mux, mux_vec, register, register_vec
from repro.core.intmlp import IntMLP
from repro.core.planner import SynthesisPlanner
from repro.core.tuning import tune_parallel


def _mlp(structure, q=5, seed=0, wmax=63):
    rng = np.random.default_rng(seed)
    ws, bs = [], []
    for a, b in zip(structure[:-1], structure[1:]):
        ws.append(rng.integers(-wmax, wmax + 1, (a, b)).astype(np.int64))
        bs.append(rng.integers(-15, 16, (b,)).astype(np.int64))
    acts = ["htanh"] * (len(structure) - 2) + ["hsig"]
    return IntMLP(ws, bs, acts, q=q)


_FIELDS = ("area_um2", "latency_ns", "energy_pj", "cycles", "clock_ns",
           "n_adders", "n_mults")

# Pre-refactor DesignReport numbers of the seed's scalar builders, captured
# before the cost-IR rewrite (floats as hex for bit-exactness).  Keyed by
# (structure, seed, wmax) fixtures over the pendigits structures.
GOLDEN = {
    ("16-16-10", 0, 63): {
        ("parallel", "behavioral"): ("0x1.cfef7ae147ac8p+16", "0x1.5199999999999p+3", "0x1.a78272ace4615p+14", 1, "0x1.5199999999999p+3", 410, 410),
        ("parallel", "cavm"): ("0x1.60cccccccccccp+12", "0x1.9999999999998p+3", "0x1.205f4c005e9f9p+10", 1, "0x1.9999999999998p+3", 1016, 0),
        ("parallel", "cmvm"): ("0x1.aa56666666668p+13", "0x1.0666666666666p+4", "0x1.6d2309f9a8f91p+11", 1, "0x1.0666666666666p+4", 594, 0),
        ("smac_neuron", "behavioral"): ("0x1.01370a3d70a3ep+14", "0x1.e2cccccccccccp+5", "0x1.b1b0bf6bbd5fdp+15", 34, "0x1.c666666666666p+0", 26, 26),
        ("smac_neuron", "mcm"): ("0x1.fb31fffffffffp+15", "0x1.b128f5c28f5c3p+6", "0x1.6aff8f82d6898p+17", 34, "0x1.97ae147ae147bp+1", 87, 0),
        ("smac_ann", "behavioral"): ("0x1.99328f5c28f5cp+12", "0x1.c273333333332p+9", "0x1.b407e5f9608fdp+18", 468, "0x1.eccccccccccccp+0", 1, 1),
        ("smac_ann", "mcm"): ("0x1.8b08cccccccc9p+13", "0x1.e335c28f5c28fp+10", "0x1.efc7310d79989p+19", 468, "0x1.0851eb851eb85p+2", 32, 0),
    },
    ("16-10-10-10", 1, 127): {
        ("parallel", "behavioral"): ("0x1.c2fd4ccccccd7p+16", "0x1.0251eb851eb84p+4", "0x1.9be4061e14001p+14", 1, "0x1.0251eb851eb84p+4", 358, 358),
        ("parallel", "cavm"): ("0x1.9be3333333331p+12", "0x1.1acccccccccccp+4", "0x1.505104f3445aep+10", 1, "0x1.1acccccccccccp+4", 985, 0),
        ("parallel", "cmvm"): ("0x1.b9c5999999995p+13", "0x1.72a3d70a3d709p+4", "0x1.78ab845ae4631p+11", 1, "0x1.72a3d70a3d709p+4", 616, 0),
        ("smac_neuron", "behavioral"): ("0x1.2da7333333334p+14", "0x1.22f0a3d70a3d8p+6", "0x1.8fee2237784d8p+15", 39, "0x1.dd70a3d70a3d8p+0", 30, 30),
        ("smac_neuron", "mcm"): ("0x1.7dd44ccccccccp+16", "0x1.08cf5c28f5c29p+7", "0x1.b352227d7d663p+17", 39, "0x1.b28f5c28f5c29p+1", 174, 0),
        ("smac_ann", "behavioral"): ("0x1.89aa3d70a3d70p+12", "0x1.a726666666667p+9", "0x1.87f7549e34bc8p+18", 420, "0x1.01eb851eb851fp+1", 1, 1),
        ("smac_ann", "mcm"): ("0x1.21d1fffffffffp+14", "0x1.c7b3333333333p+10", "0x1.5d96b3da696d6p+20", 420, "0x1.15c28f5c28f5cp+2", 61, 0),
    },
    ("16-10", 2, 63): {
        ("parallel", "behavioral"): ("0x1.5af08f5c28f63p+15", "0x1.5428f5c28f5c2p+2", "0x1.3c8c5a5b9a8ffp+13", 1, "0x1.5428f5c28f5c2p+2", 157, 157),
        ("parallel", "cavm"): ("0x1.2c6999999999ap+11", "0x1.9c28f5c28f5c1p+2", "0x1.f2830c77ffe35p+8", 1, "0x1.9c28f5c28f5c1p+2", 375, 0),
        ("parallel", "cmvm"): ("0x1.5d14cccccccccp+12", "0x1.07ae147ae147bp+3", "0x1.2c6894f476e86p+10", 1, "0x1.07ae147ae147bp+3", 227, 0),
        ("smac_neuron", "behavioral"): ("0x1.8f2999999999bp+12", "0x1.e2cccccccccccp+4", "0x1.5018155d02ba8p+14", 17, "0x1.c666666666666p+0", 10, 10),
        ("smac_neuron", "mcm"): ("0x1.8125999999998p+14", "0x1.b128f5c28f5c3p+5", "0x1.171549df87c2fp+16", 17, "0x1.97ae147ae147bp+1", 38, 0),
        ("smac_ann", "behavioral"): ("0x1.8b0b851eb8520p+11", "0x1.5519999999999p+8", "0x1.44a64fdeea97dp+16", 180, "0x1.e51eb851eb851p+0", 1, 1),
        ("smac_ann", "mcm"): ("0x1.114fffffffffdp+13", "0x1.70fffffffffffp+9", "0x1.14e5f45d41fa4p+18", 180, "0x1.0666666666666p+2", 29, 0),
    },
}


def _unhex(v):
    return float.fromhex(v) if isinstance(v, str) else v


@pytest.mark.parametrize("fixture", sorted(GOLDEN, key=str))
@pytest.mark.parametrize("engine", ["array", "scalar"])
def test_design_cost_matches_pre_refactor_golden(fixture, engine):
    """Every (arch, style) DesignReport is bit-identical to the seed."""
    sid, seed, wmax = fixture
    m = _mlp(tuple(int(x) for x in sid.split("-")), seed=seed, wmax=wmax)
    for (arch, style), want in GOLDEN[fixture].items():
        rep = design_cost(m, arch, style, engine=engine)
        got = tuple(getattr(rep, f) for f in _FIELDS)
        assert got == tuple(_unhex(v) for v in want), (arch, style, engine)


def test_array_engine_matches_scalar_on_randoms():
    """Live parity on structures/value-ranges beyond the golden pins."""
    for structure, seed, wmax in [((16, 16, 10, 10), 7, 31),
                                  ((16, 10, 10), 11, 200), ((5, 3), 4, 4),
                                  ((12, 7, 9), 13, 1000)]:
        m = _mlp(structure, seed=seed, wmax=wmax)
        for arch, style in ARCH_STYLES:
            ra = design_cost(m, arch, style, engine="array")
            rs = design_cost(m, arch, style, engine="scalar")
            for f in _FIELDS:
                assert getattr(ra, f) == getattr(rs, f), (structure, arch,
                                                          style, f)


def test_array_engine_zero_weight_edge():
    z = IntMLP([np.zeros((4, 3), np.int64)], [np.zeros(3, np.int64)],
               ["hsig"], q=3)
    for arch, style in ARCH_STYLES:
        ra = design_cost(z, arch, style, engine="array")
        rs = design_cost(z, arch, style, engine="scalar")
        for f in _FIELDS:
            assert getattr(ra, f) == getattr(rs, f)


def test_design_report_detail_tallies():
    """Array reports carry the component ledger; counts match the report."""
    m = _mlp((16, 10))
    for arch, style in ARCH_STYLES:
        rep = design_cost(m, arch, style)
        comp = rep.detail["components"]
        assert comp.get("adder", 0) == rep.n_adders
        assert comp.get("mult", 0) == rep.n_mults
        assert rep.detail["engine"] == "array"
    assert design_cost(m, "parallel", "behavioral",
                       engine="scalar").detail == {}


def test_design_cost_rejects_unknown_engine():
    m = _mlp((16, 10))
    with pytest.raises(ValueError):
        design_cost(m, "parallel", "behavioral", engine="nope")


# ---------------------------------------------------------------------------
# Cost-IR unit behavior
# ---------------------------------------------------------------------------

def test_costsheet_sequential_fold_matches_python_accumulation():
    rng = np.random.default_rng(0)
    addends = rng.uniform(0.1, 7.3, 257)
    total = 0.0
    for a in addends:
        total += float(a)
    sheet = CostSheet()
    sheet.add("x", area=addends[:100])
    sheet.add("y", area=float(addends[100]))   # scalar addend path
    sheet.add("z", area=addends[101:])
    assert sheet.fold_area() == total
    assert sheet.fold_energy() == 0.0


def test_costsheet_subtotal_is_rounded_subaccumulation():
    """add_sheet reproduces `total += layer_subtotal`, not flat concat."""
    rng = np.random.default_rng(1)
    layers = [rng.uniform(0.1, 9.9, 37) for _ in range(3)]
    expect = 0.0
    for lay in layers:
        sub = 0.0
        for a in lay:
            sub += float(a)
        expect += sub
    parent = CostSheet()
    for lay in layers:
        child = CostSheet()
        child.add("adder", area=lay, count=len(lay))
        parent.add_sheet(child, kind="layer")
    assert parent.fold_area() == expect
    assert parent.tally() == {"adder": sum(len(l) for l in layers)}


def test_vector_primitives_match_scalar_primitives():
    bits = np.arange(1, 40)
    a, d, e = adder_vec(bits)
    for i, b in enumerate(bits):
        p = adder(int(b))
        assert (a[i], d[i], e[i]) == (p.area, p.delay, p.energy)
    a, d, e = multiplier_vec(8, bits)
    for i, b in enumerate(bits):
        p = multiplier(8, int(b))
        assert (a[i], d[i], e[i]) == (p.area, p.delay, p.energy)
    a, d, e = mux_vec(16, bits)
    for i, b in enumerate(bits):
        p = mux(16, int(b))
        assert (a[i], d, e[i]) == (p.area, p.delay, p.energy)
    a, d, e = register_vec(bits)
    for i, b in enumerate(bits):
        p = register(int(b))
        assert (a[i], d, e[i]) == (p.area, p.delay, p.energy)


def test_bit_length_array_matches_int_bit_length():
    vals = np.array([0, 1, -1, 2, 3, -7, 255, -256, 1023, (1 << 60) - 1,
                     -(1 << 60)], np.int64)
    got = bit_length_array(vals)
    want = [abs(int(v)).bit_length() for v in vals]
    assert got.tolist() == want
    with pytest.raises(OverflowError):
        bit_length_array(np.array([1 << 62], np.int64))


# ---------------------------------------------------------------------------
# Planner-aware tuning (cost="adders", DESIGN.md 12.3)
# ---------------------------------------------------------------------------

def _tuning_fixture():
    rng = np.random.default_rng(5)
    mlp = IntMLP([rng.integers(-200, 201, (16, 12)).astype(np.int64),
                  rng.integers(-200, 201, (12, 10)).astype(np.int64)],
                 [rng.integers(-10, 11, 12).astype(np.int64),
                  rng.integers(-10, 11, 10).astype(np.int64)],
                 ["htanh", "hsig"], q=6)
    xv = rng.integers(-128, 128, (600, 16)).astype(np.int64)
    yv = rng.integers(0, 10, 600)
    return mlp, xv, yv


def test_cavm_column_plans_are_tnzd_affine():
    """(1, n) column plans degenerate to DBR: priced CAVM adder cost ==
    tnzd(weights) - n_columns — why planner-aware tuning prices the shared
    CMVM plan instead (see planner.cavm_adder_cost docstring)."""
    mlp, _, _ = _tuning_fixture()
    p = SynthesisPlanner()
    n_cols = sum(w.shape[1] for w in mlp.weights)
    assert p.cavm_adder_cost(mlp.weights) == tnzd(mlp.weights) - n_cols


def test_tune_parallel_adders_engine_parity_and_monotonicity():
    mlp, xv, yv = _tuning_fixture()
    rs = tune_parallel(mlp, xv, yv, max_sweeps=2, engine="serial",
                       cost="adders", planner=SynthesisPlanner())
    p = SynthesisPlanner()
    rb = tune_parallel(mlp, xv, yv, max_sweeps=2, engine="batched",
                       cost="adders", planner=p)
    assert (rs.bha, rs.replacements, rs.log) == (rb.bha, rb.replacements,
                                                 rb.log)
    for a, b in zip(rs.mlp.weights, rb.mlp.weights):
        assert np.array_equal(a, b)
    # ledger: the stats cost matches a fresh recount, and polish never
    # increased the priced cost over the phase-1 (tnzd) state
    fresh = SynthesisPlanner()
    assert rb.stats["adders_final"] == fresh.cmvm_adder_cost(rb.mlp.weights)
    assert rb.stats["adders_final"] <= rb.stats["adders_after_drop"] \
        <= rb.stats["adders_initial"]
    assert rb.stats["planner_misses"] >= 1
    assert rb.stats["tnzd_final"] == tnzd(list(rb.mlp.weights)
                                          + list(rb.mlp.biases))


def test_tune_parallel_adders_never_worse_than_tnzd_engine():
    """Phase 2 starts from the phase-1 (tnzd-identical) state and every
    polish accept is vetoed against the priced cost, so the adders engine's
    final priced CMVM cost can never exceed the tnzd engine's."""
    mlp, xv, yv = _tuning_fixture()
    p = SynthesisPlanner()
    ra = tune_parallel(mlp, xv, yv, max_sweeps=2, cost="adders", planner=p)
    rt = tune_parallel(mlp, xv, yv, max_sweeps=2, cost="tnzd")
    assert ra.stats["adders_after_drop"] == p.cmvm_adder_cost(rt.mlp.weights)
    assert ra.stats["adders_final"] <= ra.stats["adders_after_drop"]
    assert ra.bha >= rt.bha           # polish accepts still ratchet accuracy


def test_tune_parallel_rejects_unknown_cost():
    mlp, xv, yv = _tuning_fixture()
    with pytest.raises(ValueError):
        tune_parallel(mlp, xv, yv, cost="gates")


# ---------------------------------------------------------------------------
# Device TM chain (chain_engine="device", DESIGN.md 7.5 / ROADMAP)
# ---------------------------------------------------------------------------

def test_tm_chain_device_matches_host():
    pytest.importorskip("jax")
    from repro.core.tuning import tune_time_multiplexed
    rng = np.random.default_rng(2)
    ws = [(rng.integers(-40, 41, (10, 8)) * rng.integers(1, 3, (10, 8)))
          .astype(np.int64),
          (rng.integers(-40, 41, (8, 6)) * 2).astype(np.int64)]
    bs = [rng.integers(-8, 9, 8).astype(np.int64),
          rng.integers(-8, 9, 6).astype(np.int64)]
    mlp = IntMLP(ws, bs, ["htanh", "hsig"], q=5)
    xv = rng.integers(-128, 128, (250, 10)).astype(np.int64)
    yv = rng.integers(0, 6, 250)
    for scope in ("neuron", "ann"):
        th = tune_time_multiplexed(mlp, xv, yv, scope=scope, max_sweeps=2,
                                   backend="jnp", chain_engine="host")
        td = tune_time_multiplexed(mlp, xv, yv, scope=scope, max_sweeps=2,
                                   backend="jnp", chain_engine="device")
        assert (th.bha, th.replacements, th.log) == \
            (td.bha, td.replacements, td.log), scope
        for a, b in zip(th.mlp.weights + th.mlp.biases,
                        td.mlp.weights + td.mlp.biases):
            assert np.array_equal(a, b)


_TM_SHARD_SCRIPT = """
import numpy as np
from repro.core.intmlp import IntMLP
from repro.core.tuning import tune_time_multiplexed
rng = np.random.default_rng(2)
ws = [(rng.integers(-40, 41, (10, 8)) * rng.integers(1, 3, (10, 8)))
      .astype(np.int64),
      (rng.integers(-40, 41, (8, 6)) * 2).astype(np.int64)]
bs = [rng.integers(-8, 9, 8).astype(np.int64),
      rng.integers(-8, 9, 6).astype(np.int64)]
mlp = IntMLP(ws, bs, ["htanh", "hsig"], q=5)
xv = rng.integers(-128, 128, (250, 10)).astype(np.int64)  # 250 % 4: pad path
yv = rng.integers(0, 6, 250)
th = tune_time_multiplexed(mlp, xv, yv, max_sweeps=2, backend="jnp",
                           chain_engine="host")
td = tune_time_multiplexed(mlp, xv, yv, max_sweeps=2, backend="jnp",
                           shard=True, chain_engine="device")
assert (th.bha, th.replacements, th.log) == (td.bha, td.replacements, td.log)
for a, b in zip(th.mlp.weights + th.mlp.biases,
                td.mlp.weights + td.mlp.biases):
    assert np.array_equal(a, b)
import jax
assert jax.device_count() == 4
print("TM-SHARD-OK")
"""


def test_tm_chain_device_shard_map_parity():
    """The shard_map branch of the device TM chain (psum'd counts, padded
    rows) makes the same decisions as the unsharded host chain — 4 forced
    host devices, the repo's established subprocess pattern."""
    pytest.importorskip("jax")
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", _TM_SHARD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TM-SHARD-OK" in out.stdout


def test_tm_chain_device_falls_back_on_numpy_backend():
    from repro.core.tuning import tune_time_multiplexed
    mlp, xv, yv = _tuning_fixture()
    th = tune_time_multiplexed(mlp, xv, yv, max_sweeps=1, backend="numpy",
                               chain_engine="host")
    td = tune_time_multiplexed(mlp, xv, yv, max_sweeps=1, backend="numpy",
                               chain_engine="device")     # host fallback
    assert (th.bha, th.log) == (td.bha, td.log)
