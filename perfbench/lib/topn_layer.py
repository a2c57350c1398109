"""What the TopN cell's per-layer readers share."""
from . import layer, topn_bytes_model

# The per-fragment Tanimoto program on the trace's ``XLA Modules`` line.
TANIMOTO_PROGRAM = "jit_pilosa_topn_tanimoto_frag"


def roofline_pct(ctx):
    """``layer.roofline_pct`` over the bytes each request needs by its
    own text and answer (``topn_bytes_model.request_bytes``), whatever
    path the program served it by."""
    shape = ctx.config["shape"]
    need = {r["pql"]: topn_bytes_model.request_bytes(
        len(r["result"]), shape["molecules"], shape["fingerprint_bits"])
        for r in ctx.log if r.get("ok")}
    return layer.roofline_pct(ctx, need.__getitem__)
