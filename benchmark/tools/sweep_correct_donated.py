#!/usr/bin/env python3
"""``sweep_correct.py`` for a configuration whose bfloat16 weights and
their int8 copy do not fit one chip together.

    python3 benchmark/tools/sweep_correct_donated.py --config sarvam-105b \
        --seeds 24 --out chiprun_out/sweep-sarvam-105b.json

The sweep's own code runs, every argument its own, with two things put in
its place for this process: the int8-weights control is forced on
(whatever ``correct.controls`` says: such a configuration states none,
because the plain tool would run out of memory in it), and the control's
weights are made from weights DONATED to the quantizer — each bfloat16
array is quantized by the program's own ``ops.quant.quantize_tensor``
(what ``quantize_params`` calls for it: per-row scales for the two
vocabulary tables, per-output-channel for every matrix it names) and
deleted before the next, so the chip never holds the 10.92 GB tree beside
its 5.5 GB copy.  The sweep reads the bfloat16 tree no more after its
control, so nothing else changes; the rows carry
``control_int8_weights`` as the plain tool's would.  Needs the TPU."""
from __future__ import annotations

import os
import sys
from functools import partial

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (HERE, os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))):
    sys.path.insert(0, p)

import correct                                         # noqa: E402
import sweep_correct                                   # noqa: E402


def int8_weights_donated(params, tier, cfg, mesh=None):
    """``correct.int8_weights`` an array at a time, each input deleted."""
    import jax
    from distributed_llm_tpu.ops import quant

    def q(x, axis=-2):
        out = jax.block_until_ready(jax.jit(partial(
            quant.quantize_tensor, contract_axis=axis))(x))
        x.delete()
        return out

    def stack(lp):
        return {k: q(v) if k in quant._QUANT_LAYER_KEYS else v
                for k, v in lp.items()}
    out = dict(params)
    out["embed"] = q(params["embed"], -1)
    if "head" in params:
        out["head"] = q(params["head"], -1)
    for group in ("layers", "lead", "periods"):
        if isinstance(params.get(group), list):
            out[group] = [stack(lp) for lp in params[group]]
        elif group in params:
            out[group] = stack(params[group])
    return out


if __name__ == "__main__":
    correct.int8_weights = int8_weights_donated
    correct.controls_of = lambda config: ("int8_weights",)
    sys.exit(sweep_correct.main())
