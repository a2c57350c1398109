"""Layer: staging. Source: program_counter: of the lookups of the BSI
prelude memo that the window's profiled requests made, the share that hit
(``resources.bsiPreludeHits`` over hits + ``bsiPreludeMisses``): the
memo is keyed by the predicate bits and the date row, so distinct
queries read 0. Moves query_p50_ms."""
from perfbench.lib import sum_layer

read = sum_layer.prelude_hit_pct
