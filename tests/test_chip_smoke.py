"""``chip_smoke.py`` rehearsed on CPU at a tiny size: the same phases and
checks the chip run makes, with the fused kernel interpreted (off-TPU the
program would take its counted ``fused->host`` route, which the smoke
refuses). Sizes are cut here, in the test: one block instead of 32, a
population of 8, the reduced serving model."""
import json
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402
from repro.configs import all_archs  # noqa: E402
from repro.core import timing  # noqa: E402
from repro.core.ga import GAConfig  # noqa: E402

TINY_GA = GAConfig(population=8, generations=2, seed=0)


@pytest.fixture
def interpreted_fused(monkeypatch):
    real = timing.get_timing_backend

    def get(spec=None):
        if spec == "fused":
            return timing.FusedTimingBackend(interpret=True)
        return real(spec)

    monkeypatch.setattr(timing, "get_timing_backend", get)


def test_search_phase_rehearsal(interpreted_fused, capsys):
    setup = chip_smoke.search_setup(all_archs()["gpt3-7b"].llm_spec(),
                                    n_blocks=1)
    chip_smoke.search_phase(TINY_GA, setup)
    out = capsys.readouterr().out
    assert "fused == dense bitwise" in out


def test_sharded_phase_rehearsal(interpreted_fused, capsys):
    """All local devices vs one: a real mesh under the multi-device CI
    job, the one-device path otherwise."""
    n = len(jax.devices())
    setup = chip_smoke.search_setup(all_archs()["gpt3-7b"].llm_spec(),
                                    n_blocks=1)
    chip_smoke.sharded_phase(n, TINY_GA, setup)
    assert f"on {n} devices == 1 device bitwise" in capsys.readouterr().out


def test_serving_phase_rehearsal(capsys):
    chip_smoke.serving_phase(all_archs()["qwen1.5-0.5b"].reduced())
    out = capsys.readouterr().out
    assert "tokens == ServingEngine tokens" in out
    assert "serve 6 requests" in out


def test_smoke_refuses_cpu_and_prints_no_result(capsys):
    assert chip_smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert "needs a TPU" in err
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
