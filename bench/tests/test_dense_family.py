"""The dense family's ``arch_config`` and the RMSNorm epsilon: taken from
the file where the program's ``ArchConfig`` has a field for it, and
otherwise held to the program's fixed one."""
import dataclasses
import sys
import types

import pytest

from bench.lib import spec


@pytest.fixture
def dense():
    return spec.family("dense")


def test_without_an_epsilon_field_the_programs_own_is_required(dense):
    c = spec.config("internlm2_1_8b-l16")
    assert dense.arch_config(c).n_layers == 16
    with pytest.raises(ValueError, match="rms_norm_eps"):
        dense.arch_config({**c, "rms_norm_eps": 1e-5})


def test_an_epsilon_field_takes_the_files_value(dense, monkeypatch):
    from repro.configs.base import ArchConfig
    from repro.configs.internlm2_1_8b import CONFIG

    @dataclasses.dataclass(frozen=True)
    class WithEps(ArchConfig):
        rms_norm_eps: float = 1e-6

    mod = types.ModuleType("repro.configs.with_eps")
    mod.CONFIG = WithEps(**dataclasses.asdict(CONFIG))
    monkeypatch.setitem(sys.modules, "repro.configs.with_eps", mod)
    c = {**spec.config("internlm2_1_8b-l16"), "program_config": "with_eps",
         "rms_norm_eps": 1e-5}
    cfg = dense.arch_config(c)
    assert isinstance(cfg, WithEps) and cfg.rms_norm_eps == 1e-5
