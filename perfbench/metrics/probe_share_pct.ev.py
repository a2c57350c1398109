"""Layer: tier choice. Source: program_counter: the wall time of the
path model's looks at the loser (``/debug/vars`` ``pathModel``:
``probeMs`` a call shape, after the window minus before it, summed) as a
share of the window. 0 where no probe ran; None where the program has
no such counter. The attempts run under span ``path.probe``; their
seconds go to stderr beside the counts (``path_probes``). The probes lie
above the 95th percentile while they are under 5 % of the requests: the
nearest metric the benchmark has. Moves query_p95_ms."""
from perfbench.lib import chains

read = chains.probe_share_pct
