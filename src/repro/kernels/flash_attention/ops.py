"""Registry bindings for attention (operation ``nn_attention``).

One skeleton serves all three kernel spaces (``instantiate_common`` — the
``common/`` folder idiom); the Pallas instantiation resolves its block
geometry through the executor's launch-configuration table instead of
hard-coding tile sizes.
"""

from __future__ import annotations

from typing import Optional

from repro.core import registry, tuning
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import mha_ref


def _vmem_bytes(shapes, block) -> int:
    """Working set per grid step: q/k/v/o tiles + f32 scratch (m, l, acc) +
    the (block_q, block_kv) score tile."""
    bq, bkv = block["block_q"], block["block_kv"]
    d = shapes.get("D", 128)
    itemsize = shapes.get("itemsize", 4)
    tiles = (bq + 2 * bkv + bq) * d * itemsize
    scratch = bq * (128 * 2 + d) * 4
    scores = bq * bkv * 4
    return tiles + scratch + scores


def _constrain(hw, shapes, block):
    # power-of-two tiles keep the MXU happy and the shrink loop simple
    return {
        key: tuning.prev_pow2(max(int(block[key]), hw.sublane_count))
        for key in ("block_q", "block_kv")
    }


ATTENTION_SPEC = tuning.register_spec(
    tuning.TuningSpec(
        op="nn_attention",
        params=("block_q", "block_kv"),
        seed=lambda hw: {
            "block_q": max(hw.mxu_dim, 128),
            "block_kv": max(hw.mxu_dim, 128),
        },
        vmem_bytes=_vmem_bytes,
        constrain=_constrain,
        floors={"block_q": 8, "block_kv": 8},
    )
)

# kv-chunk length of the chunked-scan xla attention variant
# (repro.nn.attention.attention_xla_chunked): a launch parameter like any
# other — resolved per target when cfg.attn_chunk is None.  The scan never
# materializes (S, Skv), so the budget driver is just the per-chunk score block.
CHUNKED_ATTENTION_SPEC = tuning.register_spec(
    tuning.TuningSpec(
        op="nn_attention_chunked",
        params=("chunk",),
        seed=lambda hw: {"chunk": max(hw.lane_count * 4, 512)},
        vmem_bytes=lambda shapes, block: 4
        * block["chunk"]
        * (shapes.get("S", 512) + 2 * shapes.get("D", 128)),
        constrain=lambda hw, shapes, block: {
            "chunk": max(
                int(block["chunk"]) - int(block["chunk"]) % hw.lane_count,
                hw.lane_count,
            )
        },
        floors={"chunk": 128},
    )
)


def _attention_skeleton(
    ex, q, k, v, causal: bool = True, scale: Optional[float] = None, *, variant: str
):
    if variant != "pallas":
        # dense-materialized attention; XLA fuses but the S x Skv score matrix
        # hits HBM — the Pallas kernel is the memory-saving path
        return mha_ref(q, k, v, causal=causal, scale=scale)
    cfg = ex.launch_config(
        "nn_attention",
        {
            "S": q.shape[2],
            "Skv": k.shape[2],
            "D": q.shape[-1],
            "itemsize": q.dtype.itemsize,
        },
    )
    return flash_attention(
        q,
        k,
        v,
        causal=causal,
        scale=scale,
        block_q=cfg["block_q"],
        block_kv=cfg["block_kv"],
        interpret=ex.interpret,
    )


attention_op = registry.instantiate_common(
    "nn_attention",
    _attention_skeleton,
    {
        "reference": dict(variant="reference"),
        "xla": dict(variant="xla"),
        "pallas": dict(variant="pallas"),
    },
)
attention_op.__doc__ = "softmax attention (B,Hq,S,D)x(B,Hkv,Skv,D) -> (B,Hq,S,D)"
