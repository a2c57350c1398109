"""Layer: fragment selection. Source: program_counter: of the
per-fragment TopN scans with a src that the window's profiled requests
made, the share whose top n was selected inside the scan's program
(``resources.topnSelectDevice``) and not on the host over a count a row
(``topnSelectHost``: explicit ids, an attribute filter, no ``n``) nor on
the host after the device's selection overflowed its bucket
(``topnSelectOverflow``: more rows tied at the cut than it holds, a
second launch). None where no profile has the keys (an older program)
or no scan had a src. Moves query_p50_ms."""
from perfbench.lib import spans

KEYS = ("topnSelectDevice", "topnSelectHost", "topnSelectOverflow")


def read(ctx):
    device, host, overflow = (spans.resources_sum(ctx, k) for k in KEYS)
    if None in (device, host, overflow) or device + host + overflow == 0:
        return None
    return 100.0 * device / (device + host + overflow)
