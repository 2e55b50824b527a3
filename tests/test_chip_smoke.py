"""``chip_smoke.py`` off the chip: it refuses the CPU, and its phase
functions pass their own checks at smoke sizes."""

import jax
import pytest

import chip_smoke
from repro.configs.registry import get_smoke_config
from repro.launch.mesh import make_host_mesh


def test_main_refuses_without_a_tpu(capsys):
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached")
    rc = chip_smoke.main([])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok": true' not in out


def test_serve_phase_teacher_forced_check_passes():
    cfg = get_smoke_config(chip_smoke.SERVE_ARCH)
    run, tf = chip_smoke.serve_phase(
        cfg, make_host_mesh(data=1, model=1), batch=2, prompt_len=16, gen=4
    )
    assert run.generated.shape == (2, 5)
    assert run.decode_logits.shape == (4, 2, cfg.vocab_size)
    assert tf.shape == (2, cfg.vocab_size)


def test_sharded_serve_phase_matches_single_device():
    if jax.device_count() < 4:
        pytest.skip("needs 4 host devices")
    chip_smoke.sharded_serve_phase(get_smoke_config(chip_smoke.SERVE_ARCH), seed=0)


def test_executor_phase_parity_at_8_cores():
    chip_smoke.executor_phase(8, iters=4)
