"""chip_smoke.py refuses every run that would not exercise the chip's kernels."""
import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(smoke, monkeypatch, capsys):
    monkeypatch.delenv("REPRO_INTERPRET", raising=False)
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert "no TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""  # no verdict line, no phase output


@pytest.mark.parametrize("route", ["1", "0"])
def test_refuses_a_forced_kernel_route(smoke, monkeypatch, route):
    monkeypatch.setenv("REPRO_INTERPRET", route)
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert "forces a kernel route" in str(e.value.code)


def test_candidate_exchange_matches_either_collective(smoke):
    gather = "%all-gather.1 = f32[4000]{0} all-gather(f32[1000]{0} %x), dimensions={0}"
    reduce = "%all-reduce.3 = s32[4000]{0:T(1024)} all-reduce(s32[4000]{0} %y), to_apply=%add"
    assert smoke.candidate_exchange(gather, 4000)
    assert smoke.candidate_exchange(reduce, 4000)
    assert not smoke.candidate_exchange(reduce, 1000)
    assert not smoke.candidate_exchange("%add.2 = f32[4000]{0} add(%a, %b)", 4000)
