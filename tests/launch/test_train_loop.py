"""Launch-layer integration tests (multi-device subprocesses): the full
production trainer on a debug mesh — sharded step, checkpoint/resume,
elastic mesh change, preemption — and a miniature dry-run."""
import pytest

from tests._subproc import check_snippet

TRAIN_SNIPPET = r"""
from repro.launch.train import TrainLoopConfig, train
out = train(TrainLoopConfig(arch="qwen2-1.5b", steps=12, seq_len=64,
                            global_batch=4, mesh_shape=(2, 2),
                            log_every=100))
assert out["last_step"] == 12, out
assert out["final_loss"] < out["losses"][0], out["losses"]
print("TRAIN_MESH_OK", out["final_loss"])
"""


RESUME_SNIPPET = r"""
import tempfile
from repro.launch.train import TrainLoopConfig, train
d = tempfile.mkdtemp()
cfg = TrainLoopConfig(arch="internlm2-1.8b", steps=6, seq_len=64,
                      global_batch=4, mesh_shape=(2, 2), ckpt_dir=d,
                      ckpt_every=3, log_every=100, lr=2e-2,
                      warmup_steps=1)
out1 = train(cfg)
# Elastic restart: resume the SAME run on a DIFFERENT mesh layout.
cfg2 = TrainLoopConfig(arch="internlm2-1.8b", steps=10, seq_len=64,
                       global_batch=4, mesh_shape=(4, 1), ckpt_dir=d,
                       ckpt_every=3, log_every=100, lr=2e-2,
                       warmup_steps=1)
out2 = train(cfg2)
assert out2["last_step"] == 10, out2
# The resumed run continues from the trained state: its first losses sit
# near out1's final loss, well below the fresh-init loss.
assert out2["losses"][0] < out1["losses"][0] - 0.1, (out1, out2)
assert out2["final_loss"] < out1["losses"][0]
print("RESUME_ELASTIC_OK", out1["final_loss"], out2["final_loss"])
"""


DRYRUN_TINY_SNIPPET = r"""
import dataclasses, jax
from repro.configs import get_config, reduced_config
from repro.configs.base import ShapeConfig
from repro.launch.steps import make_cell_plan
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = dataclasses.replace(reduced_config(get_config("deepseek-moe-16b")),
                          tp_size=2)
for shape in (ShapeConfig("t", 64, 4, "train"),
              ShapeConfig("p", 64, 4, "prefill"),
              ShapeConfig("d", 64, 4, "decode")):
    with mesh:
        plan = make_cell_plan(cfg, mesh, shape)
        compiled = plan.step_fn.lower(*plan.args).compile()
        cost = analyze_hlo(compiled.as_text())
        assert cost["flops"] > 0, (shape, cost)
        assert plan.per_chip_argument_bytes() > 0
print("DRYRUN_TINY_OK")
"""


@pytest.mark.subproc
def test_trainer_on_debug_mesh():
    out = check_snippet(TRAIN_SNIPPET, n_devices=4, timeout=580)
    assert "TRAIN_MESH_OK" in out


@pytest.mark.subproc
def test_checkpoint_resume_elastic_mesh_change():
    out = check_snippet(RESUME_SNIPPET, n_devices=4, timeout=580)
    assert "RESUME_ELASTIC_OK" in out


@pytest.mark.subproc
def test_tiny_multipod_dryrun_all_step_kinds():
    out = check_snippet(DRYRUN_TINY_SNIPPET, n_devices=8, timeout=580)
    assert "DRYRUN_TINY_OK" in out
