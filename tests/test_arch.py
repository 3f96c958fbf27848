"""The architecture description (``repro.configs.arch``): every registered
config has an entry, and the planner and data layer read from it exactly
the numbers recorded before it replaced the per-module family branches."""
import dataclasses

import pytest

from repro.configs.arch import ARCHS, arch_of, layer_slots
from repro.configs.registry import ARCH_IDS, all_configs, get_config
from tests import _arch_golden as _golden


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_numbers_match_golden(arch):
    """Profile, cache bytes, shape support, replan candidates, input specs
    and model class, floats bit for bit."""
    got = _golden.arch_entry(arch)
    want = _golden.load()["arch"][arch]
    assert got.keys() == want.keys()
    for key in want:
        assert _golden.canonical(got[key]) == _golden.canonical(want[key]), key


def test_every_registered_family_has_an_arch():
    families = {cfg.family for cfg in all_configs().values()}
    assert families <= ARCHS.keys()
    for cfg in all_configs().values():
        slots = layer_slots(cfg)
        assert slots and all(s.kind for s in slots)
        a = arch_of(cfg)
        assert not a.pp or a.stacked          # the pipeline stages a stack
        assert a.block_kind in {s.kind for s in slots}
    bad = dataclasses.replace(get_config("qwen3-14b"), family="nope")
    with pytest.raises(ValueError, match="unknown family"):
        arch_of(bad)
