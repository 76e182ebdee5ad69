"""The share of its held experts that a decode tick's counted rows
reached, of a served model that holds a share of a wider router's experts:
`engine_moe_experts_read_total` over `engine_moe_experts_offered_total`
between the first and the last `serve_ticks` record inside the window (the
engine's cumulative pair `moe_experts`: [the held experts a decoding row
reached, the held experts there were], over the decode ticks and all
expert layers). The router's count, `sum(group_sizes > 0)`: what the
experts' kernels MAY leave unread, not what they did. A tree whose
`moe_gmm` fetched an empty group's matrices all the same would read the
same share; that the kernel followed it shows in the trace alone
(`moe_experts_decode_ms_per_step` falls with this share). It falls with
the rows a tick decodes and with a skewed router; 1.0 means every tick's
rows reached every held expert. None where the records lack the pair (a
model that holds every expert or none, a parent commit)."""

from benchmark.harness import serve_journal


def read(run):
    snaps = [r["moe_experts"]
             for r in serve_journal.of_kind(run, "serve_ticks")
             if "moe_experts" in r]
    if len(snaps) < 2 or snaps[-1][1] <= snaps[0][1]:
        return None
    return (snaps[-1][0] - snaps[0][0]) / (snaps[-1][1] - snaps[0][1])
