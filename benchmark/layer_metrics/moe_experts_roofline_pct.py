"""Share of the roofline the expert matmuls reach: the least time the
chip could take for what the experts of a step NEED to do
(kernel_costs/moe_experts.py: the routed rows through each expert's two
matrices, forward and the two backward products, weights and rows moved
once a pass; from the cell's configuration and traffic, peaks from
benchmark/peaks.json) over the time `moe_experts_ms_per_step` reads from
the trace (the scope `moe_experts` and XLA's `ragged-dot-none` kernels;
recomputation included). The bound that applies, compute or
memory, goes to the line's `extras.roofline`. None in a rehearsal (no
peaks), on an untraced run, or where nothing ran under the scope."""


def read(run):
    measured_ms = run.cell.reader("moe_experts_ms_per_step")(run)
    needed = run.cell.kernel_cost("moe_experts")
    if not measured_ms or run.peaks is None or needed is None:
        return None
    mix, config = run.cell.traffic, run.cell.config
    rows = (mix["micro_batch_size"] * mix["seq_length"]
            * config["num_experts_per_tok"])
    calls = (mix["global_batch_size"] // mix["micro_batch_size"]
             * config["num_hidden_layers"])
    flops, nbytes = needed((rows, config["hidden_size"]), 2, config)
    compute_s = calls * flops / run.peaks["bf16_flops_per_s"]
    memory_s = calls * nbytes / run.peaks["hbm_bytes_per_s"]
    needed_ms = 1e3 * max(compute_s, memory_s)
    run.extras.setdefault("roofline", {})["moe_experts"] = {
        "pct": 100.0 * needed_ms / measured_ms,
        "bound": "compute" if compute_s >= memory_s else "memory",
        "needed_flop": calls * flops, "needed_bytes": calls * nbytes,
        "needed_ms": needed_ms, "measured_ms": measured_ms}
    return 100.0 * needed_ms / measured_ms
