"""Share of the roofline the held experts' matmuls reach, of a model that
holds a share of a wider router's experts: the least time the chip could
take for what the experts held here NEED to do a step
(kernel_costs/moe_held_experts.py: the rows the router sent them, as the
trainer counted them (`moe_held_rows_share` x micro-batch x sequence x
experts a token), through each expert's two matrices, forward and the two
backward products, the held experts' weights and the rows moved once a
pass; peaks from benchmark/peaks.json) over the time
`moe_experts_ms_per_step` reads from the trace (the scope `moe_experts`:
the grouped kernels, the activation and the zeroing of the buffer's rows
behind the last group). `moe_experts_roofline_pct` would count every
routed row and read `intermediate_size` as the expert's width. The bound
that applies goes to the line's `extras.roofline`. None in a rehearsal (no
peaks), on an untraced run, where nothing ran under the scope, or where
the journal carries no count of held rows."""


def read(run):
    measured_ms = run.cell.reader("moe_experts_ms_per_step")(run)
    share = run.cell.reader("moe_held_rows_share")(run)
    needed = run.cell.kernel_cost("moe_held_experts")
    if not measured_ms or not share or run.peaks is None or needed is None:
        return None
    mix, config = run.cell.traffic, run.cell.config
    micro_batches = mix["global_batch_size"] // mix["micro_batch_size"]
    rows = (share * mix["micro_batch_size"] * mix["seq_length"]
            * config["num_experts_per_tok"])
    work = needed((rows, config["hidden_size"], micro_batches), 2, config)
    if work is None:
        return None
    calls = micro_batches * config["num_hidden_layers"]
    compute_s = calls * work[0] / run.peaks["bf16_flops_per_s"]
    memory_s = calls * work[1] / run.peaks["hbm_bytes_per_s"]
    needed_ms = 1e3 * max(compute_s, memory_s)
    run.extras.setdefault("roofline", {})["moe_held_experts"] = {
        "pct": 100.0 * needed_ms / measured_ms,
        "bound": "compute" if compute_s >= memory_s else "memory",
        "needed_flop": calls * work[0], "needed_bytes": calls * work[1],
        "needed_ms": needed_ms, "measured_ms": measured_ms}
    return 100.0 * needed_ms / measured_ms
