#!/usr/bin/env python
"""fp8 capability probe for the reachable TPU generation.

Answers two questions on real hardware and prints the evidence JSON:
  1. Does XLA keep f8 operand types in the compiled dot (native fp8 MXU
     path), or does it insert converts (fp8 numerics at bf16 speed)?
     Decided by inspecting the optimized HLO for the dot's operand types.
  2. What is the measured step-time ratio of the fp8-hybrid vs bf16 tiny
     train step (ops/fp8.py path end to end)?

Prints one JSON object (device named) and leaves it, with the optimized
HLO it judged, under chiprun_out/fp8_probe/. On the chip:
    chiprun -- python tools/fp8_probe.py
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _f8_dot_survives(hlo: str) -> bool:
    """Do f8 operand types reach a dot in the optimized HLO?

    Parses instruction definitions (`%name = dtype[...] op(...)`) into a
    name->dtype map, then checks the operands of every dot/fusion-with-dot
    against it. A `convert` whose OPERAND is f8 and result is wider means
    XLA inserted an upcast (emulated path). Operand names alone are
    checked — HLO's text printer does not repeat operand types inline —
    so this cannot false-positive on a coincidental f8 string elsewhere.

    The `%` sigil is optional on both definition LHS and operands (newer
    XLA text printers omit it); names are normalized before lookup.
    """
    import re

    dtype_of = {}
    for m in re.finditer(r"%?([\w.\-]+)\s*=\s*([a-z0-9]+)\[", hlo):
        dtype_of[m.group(1)] = m.group(2)

    def dt(name: str) -> str:
        return dtype_of.get(name.lstrip("%"), "")

    upcast_from_f8 = False
    for m in re.finditer(r"=\s*([a-z0-9]+)\[[^\]]*\]\{?[^=]*?convert\((%?[\w.\-]+)\)",
                         hlo):
        res_dt, operand = m.group(1), m.group(2)
        if dt(operand).startswith("f8") and not res_dt.startswith("f8"):
            upcast_from_f8 = True

    dot_has_f8 = False
    for m in re.finditer(r"\bdot\(\s*(%?[\w.\-]+)\s*,\s*(%?[\w.\-]+)", hlo):
        if any(dt(op).startswith("f8") for op in m.groups()):
            dot_has_f8 = True
    return dot_has_f8 and not upcast_from_f8


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from megatron_tpu.platform import device_summary

    backend = jax.default_backend()
    out = {"device": device_summary()}
    out_dir = os.path.join(REPO, "chiprun_out", "fp8_probe")
    os.makedirs(out_dir, exist_ok=True)

    # --- 1. HLO inspection: does the f8 dot survive compilation? -------
    def dot(x, w):
        return jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    x8 = jnp.zeros((256, 256), jnp.float8_e4m3fn)
    w8 = jnp.zeros((256, 256), jnp.float8_e4m3fn)
    compiled = jax.jit(dot).lower(x8, w8).compile()
    hlo = compiled.as_text()
    out["f8_dot_operands_survive"] = _f8_dot_survives(hlo)
    out["hlo_has_f8"] = "f8e4m3" in hlo
    # drop the HLO next to the verdict so the classification is auditable
    with open(os.path.join(out_dir, "fp8_probe_hlo.txt"), "w") as f:
        f.write(hlo)

    # --- 2. end-to-end: fp8-hybrid vs bf16 tiny train-step time --------
    from megatron_tpu.models import presets
    from megatron_tpu.models.language_model import lm_loss
    from megatron_tpu.models.params import init_params

    # real geometry on TPU; a shrunken smoke geometry elsewhere (the CPU
    # run only proves the tool end-to-end, not a meaningful ratio)
    tpu = backend == "tpu"
    V, S, H, L, F = ((2048, 512, 512, 4, 1408) if tpu
                     else (256, 64, 64, 2, 176))

    def step_time(fp8_format):
        cfg = presets.tiny(vocab_size=V, seq_length=S, hidden_size=H,
                           num_layers=L, num_attention_heads=8,
                           ffn_hidden_size=F, params_dtype="bfloat16",
                           fp8_format=fp8_format)
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, V, (4, S)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, V, (4, S)), jnp.int32),
            "loss_mask": jnp.ones((4, S), jnp.float32)}
        f = jax.jit(jax.grad(lambda p: lm_loss(cfg, p, batch)[0]))
        jax.block_until_ready(f(params))  # compile
        t0 = time.perf_counter()
        n = 10
        for _ in range(n):
            g = f(params)
        jax.block_until_ready(g)
        return (time.perf_counter() - t0) / n

    t_bf16 = step_time(None)
    t_fp8 = step_time("hybrid")
    out["bf16_step_s"] = round(t_bf16, 5)
    out["fp8_hybrid_step_s"] = round(t_fp8, 5)
    out["fp8_speedup"] = round(t_bf16 / t_fp8, 3)

    with open(os.path.join(out_dir, "fp8_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
