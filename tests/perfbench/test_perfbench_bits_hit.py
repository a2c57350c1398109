"""``bsi_bits_hit_pct.ssb``: the share of the predicate-bit lookups of the
window's profiled requests that found the vector already on the device,
read from the ``resources`` blocks alone; None on a program whose
profiles lack the keys, and where no lookup was made."""
import importlib.util
import os
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _reader():
    path = os.path.join(ROOT, "perfbench", "metrics",
                        "bsi_bits_hit_pct.ssb.py")
    spec = importlib.util.spec_from_file_location("bits_hit_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("profiles, want", [
    ([{"bsiBitsHits": 4, "bsiBitsMisses": 0}] * 3, 100.0),
    ([{"bsiBitsHits": 0, "bsiBitsMisses": 4},
      {"bsiBitsHits": 3, "bsiBitsMisses": 1}], 37.5),
    ([{"bsiBitsHits": 0, "bsiBitsMisses": 3}], 0.0),
    # A request answered from the prelude memo makes no lookup.
    ([{"bsiBitsHits": 0, "bsiBitsMisses": 0},
      {"bsiBitsHits": 1, "bsiBitsMisses": 1}], 50.0),
    ([{"bsiBitsHits": 0, "bsiBitsMisses": 0}], None),
    ([{"slices": 172, "bsiPreludeMisses": 1}], None),   # the parent
    ([], None),
])
def test_the_resident_bits_hit_share(profiles, want):
    ctx = SimpleNamespace(log=[{"ok": True, "profile": {"resources": p}}
                               for p in profiles])
    assert _reader()(ctx) == want


def test_a_failed_request_is_not_read():
    ctx = SimpleNamespace(log=[
        {"ok": True, "profile": {"resources": {"bsiBitsHits": 3,
                                               "bsiBitsMisses": 1}}},
        {"ok": False, "profile": {"resources": {"bsiBitsHits": 0,
                                                "bsiBitsMisses": 9}}}])
    assert _reader()(ctx) == 75.0
