"""The FLOP counts against hand counts at small shapes."""
import importlib.util
import os

from bench import flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config_module(name):
    spec = importlib.util.spec_from_file_location(
        "cfg_" + name.replace("-", "_"),
        os.path.join(BENCH, "configs", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_mlp_passes_by_hand():
    mod = config_module("timit-fig5")
    cfg = {"layer_dims": [3, 4, 2]}
    mms = mod.matmuls(cfg, 5)
    # x·W1 (5x3x4, data input) and h·W2 (5x4x2)
    assert mms == [(2 * 5 * 3 * 4, 1), (2 * 5 * 4 * 2, 2)]
    f1, f2 = 120.0, 80.0
    assert flops.forward(mms) == f1 + f2
    # backward: dW1 only for the first layer; dW2 and dH for the second
    assert flops.gradient(mms) == (f1 + f1) + (f2 + 2 * f2)
    # R-op: 2 products for the data-input layer, 6 for the other
    assert flops.hvp(mms) == 2 * f1 + 6 * f2


def test_hf_step_by_hand():
    mod = config_module("timit-fig5")
    cfg = {"layer_dims": [3, 4, 2]}
    full, curv = mod.matmuls(cfg, 8), mod.matmuls(cfg, 2)
    f_full = 2 * 8 * 3 * 4 + 2 * 8 * 4 * 2          # 192 + 128
    f_curv = 2 * 2 * 3 * 4 + 2 * 2 * 4 * 2          # 48 + 32
    grad_full = 2 * 192 + 3 * 128
    grad_curv = 2 * 48 + 3 * 32
    hvp_curv = 2 * 48 + 6 * 32
    want = grad_full + grad_curv + (1 + 2 * 5) * hvp_curv + 3 * f_full
    assert f_curv == 80
    assert flops.hf_step(full, curv, cg_iters=5, ls_evals=3) == want


def test_timit_forward_at_full_width():
    mod = config_module("timit-fig5")
    cfg = {"layer_dims": [360, 512, 512, 512, 1973]}
    per_row = 2 * (360 * 512 + 512 * 512 * 2 + 512 * 1973)
    assert flops.forward(mod.matmuls(cfg, 1)) == per_row
