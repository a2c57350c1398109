"""Layer: TopN phases. Source: program_counter: of the multi-slice TopN
phase 2s of the window's profiled requests, the share read from the
count matrices the batched phase 1 fetched (``resources.
topnRecountsDerived`` over derived + ``topnRecountsRun``): a derived
phase 2 runs no second pass over the 339 slices, a run one does (a
serial phase 1, the path model's looks at the loser among them). None
where no profile has the keys (a program that always recounts) or no
phase 2 was reached. Moves query_p50_ms."""
from perfbench.lib import spans


def read(ctx):
    derived = spans.resources_sum(ctx, "topnRecountsDerived")
    run = spans.resources_sum(ctx, "topnRecountsRun")
    if derived is None or run is None or derived + run == 0:
        return None
    return 100.0 * derived / (derived + run)
