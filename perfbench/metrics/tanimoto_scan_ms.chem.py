"""Layer: kernels. Source: device_trace: device milliseconds a launch of the
per-fragment Tanimoto program, named ``jit_pilosa_topn_tanimoto_frag*`` on
the trace's ``XLA Modules`` line. Moves query_p50_ms."""
from perfbench.lib import spans, topn_layer


def read(ctx):
    return spans.module_ms(ctx, topn_layer.TANIMOTO_PROGRAM)
