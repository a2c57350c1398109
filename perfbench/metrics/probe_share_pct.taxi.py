"""Layer: tier choice. Source: program_counter: the wall time of the
path model's looks at the loser (``/debug/vars`` ``pathModel``:
``probeMs`` a call shape, after the window minus before it, summed) as a
share of the window. At 339 slices a TopN's shapes (both phases) are
probed serially, one request in 64 each. 0 where no probe ran; None
where the program has no such counter. Moves query_p95_ms."""
from perfbench.lib import chains

read = chains.probe_share_pct
