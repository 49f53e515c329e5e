"""Operation and byte counts from shapes, and the table of peaks."""
import json
import os

import pytest

from bench import flops, reflib, spec as spec_lib
from bench.tests import tiny


def _config(name):
    with open(os.path.join(spec_lib.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _ref(name):
    if name == "tiny-ssm":
        path = os.path.join(os.path.dirname(__file__), "mamba2_ref.py")
        return tiny.TINY_SSM, spec_lib.load_module(path, "mamba2_ref")
    path = os.path.join(spec_lib.BENCH, "configs", name + ".py")
    return _config(name), spec_lib.load_module(path, "ref_" + name)


@pytest.mark.parametrize("name,want", [
    # 6 x (12 layers x 9,437,184 + head 768 x 32768) + 3 x 12 x 4 x 12 x 64
    # x 1024 (causal attention); the input embedding gather is not counted
    ("paper-lm-100m", 6 * (12 * 9_437_184 + 768 * 32768)
     + 3 * 12 * 4 * 12 * 64 * 1024),
    # 6 x (3 layers x (in_proj 64 x (2 x 128 + 2 x 16 + 8) + out_proj 128
    # x 64) + tied head 64 x 256) + 3 x 3 layers x (Q N 16 x 16 + Q H P
    # 16 x 8 x 16 + 4 H P N 4 x 8 x 16 x 16), chunk Q 16
    ("tiny-ssm", 6 * (3 * (64 * 296 + 128 * 64) + 64 * 256)
     + 3 * 3 * (16 * 16 + 16 * 8 * 16 + 4 * 8 * 16 * 16)),
])
def test_model_flops_per_token(name, want):
    config, ref = _ref(name)
    assert ref.flops_per_token(config["model"], config["seq"]) == want


_API_STUBS = {"param_shapes": "def param_shapes(model):\n    return {}\n",
              "loss_sum": "def loss_sum(*args):\n    return 0.0\n",
              "flops_per_token": "def flops_per_token(model, seq):\n"
                                 "    return 1.0\n"}


def test_model_flops_values(tmp_path):
    config, ref = _ref("paper-lm-100m")
    assert ref.flops_per_token(config["model"], config["seq"]) \
        == 943_718_400
    # a reference that lacks a part of the API is refused when the cell is
    # loaded, before anything compiles
    root = tiny.make_root(tmp_path)
    spec = spec_lib.load_spec(root)
    path = os.path.join(root, "bench", "configs", "tiny-lm.py")
    for missing in spec_lib.MODEL_REF_API:
        with open(path, "w") as f:
            f.write("".join(v for k, v in _API_STUBS.items()
                            if k != missing))
        with pytest.raises(spec_lib.SpecError,
                           match=rf"tiny-lm\.py defines no {missing}"):
            spec_lib.Cell(spec, "tiny-lm.adam", root)


def _every_cell(tmp_path):
    tiny_root = tiny.make_root(tmp_path)
    for root in (spec_lib.ROOT, tiny_root):
        spec = spec_lib.load_spec(root)
        for w in spec["workloads"]:
            yield spec_lib.Cell(spec, w["name"], root)


def test_shape_counts_build_for_every_cell(tmp_path):
    """What a traced run computes from shapes alone, for the benchmark's
    cells and the tiny dense and state-space ones."""
    names = []
    for cell in _every_cell(tmp_path):
        counts = flops.shape_counts(cell)
        assert counts["flops_per_token"] > 0, cell.name
        assert set(counts) == {"flops_per_token", "pool_groups", "rank"}
        assert all(n > 0 for n in counts["pool_groups"].values())
        names.append(cell.name)
    assert {"paper-lm-100m.sketchy", "tiny-ssm.sketchy",
            "tiny-ssm.adam"} <= set(names)


def test_gram_and_apply_costs():
    # C = M^T M for two (3, 4) blocks: 2*N*d*k^2 ops; read M, write C
    assert flops.gram_cost(2, 3, 4) == (192, 4 * 2 * (12 + 16))
    # U^T G and U (c * .) for U (2, 3, 2), G (2, 3, 5): 4*N*d*ell*n ops;
    # read U, G, c, base, write the output
    assert flops.apply_cost(2, 3, 2, 5) == (240, 4 * 2 * (6 + 30 + 2 + 1))


def test_pool_groups_and_calls():
    ref = spec_lib.load_module(
        os.path.join(spec_lib.BENCH, "configs", "paper-lm-100m.py"), "plm")
    import jax
    shapes = jax.tree.leaves(ref.param_shapes(_config("paper-lm-100m")["model"]),
                             is_leaf=lambda x: isinstance(x, tuple))
    groups = flops.pool_groups(shapes, 1024)
    # attention 4 x 12 blocks; embed 32 + w_down 36; lm_head 32 + w_gate 36
    # + w_up 36; the two stacked norms one block each
    assert groups == {(768, 768): 48, (1024, 768): 68, (768, 1024): 104,
                      (12, 768): 2}
    calls = flops.gram_calls(groups, 64)
    assert (48, 768, 832) in calls and (68, 768, 1088) in calls
    assert (2, 12, 780) in calls and (2, 768, 76) in calls
    assert (104, 768, 64, 1024) in flops.apply_calls(groups, 64)


def test_block_layout():
    assert reflib.block_layout((768,), 1024) is None
    assert reflib.block_layout((5, 1), 1024) is None
    assert reflib.block_layout((12, 3072, 768), 1024) \
        == (12, 3072, 768, 1024, 768, 3, 1)


def test_least_seconds_names_its_bound():
    peak = flops.peaks("TPU v5 lite")
    t, bound = flops.least_seconds((197e12, 1.0), peak)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = flops.least_seconds((1.0, 819e9), peak)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_peaks_table():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 394e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    assert p["ici_bits_per_s"] == 1600e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")
    with pytest.raises(KeyError):
        flops.peaks("cpu")
