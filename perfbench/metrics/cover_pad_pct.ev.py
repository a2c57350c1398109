"""Layer: kernels. Source: program_counter: of the operands that the plans
of the window's profiled requests gave their time Ranges
(``resources.rangeCoverOperands``), the share that holds a view of the
cover again (operands less ``resources.rangeCoverViews``): what
bucketing a cover's width reads twice. Moves query_p50_ms."""
from perfbench.lib import cover_layer

read = cover_layer.cover_pad_pct
