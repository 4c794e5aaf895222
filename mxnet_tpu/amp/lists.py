"""AMP op lists (parity: python/mxnet/contrib/amp/lists/symbol.py).

Three classes, mirroring the reference's FP16_FUNCS / FP32_FUNCS /
WIDEST_TYPE_CASTS (``amp.py:161-195``), retargeted at bfloat16 — the
MXU-native low-precision dtype (no loss scaling strictly required, but a
dynamic scaler is provided for float16 parity).
"""

# compute-bound ops that run in the target (low-precision) dtype —
# these are the MXU matmul/conv consumers
TARGET_DTYPE_OPS = [
    "FullyConnected",
    "Convolution",
    "Deconvolution",
    "dot",
    "batch_dot",
    "_linalg_gemm",
    "_linalg_gemm2",
    "RNN",
    "_npi_einsum",
    "_contrib_interleaved_matmul_selfatt_qk",
    "_contrib_interleaved_matmul_selfatt_valatt",
    # flash attention: bf16 in/out is safe — the Pallas kernel upcasts
    # per-block and accumulates softmax/output in f32 internally; f32
    # inputs would double attention HBM traffic and halve MXU rate
    # (xplane r5: f32[96,512,64] custom-calls before this entry)
    "_contrib_flash_attention",
    # the same kernels over latent attention's operands in the projections'
    # own layout (ops/mla_kernels.py): the queries' rotation is float32
    # inside, from float32 angles
    "_contrib_mla_flash_attention",
    # the same kernels under the block-diffusion mask over the projections'
    # own layout (ops/bd_kernels.py): the qk-norm and the rotation are
    # float32 inside, the gains kept as they come (below)
    "_contrib_bd_flash_attention",
    # the experts' grouped products; the routing weights stay as they
    # came (TARGET_DTYPE_KEEP below)
    "_contrib_moe_grouped_ffn",
]

# inputs of a target-dtype op, by position, that keep the dtype they came
# in: ``_contrib_moe_grouped_ffn``'s ``topk_weight`` (input 2) scales whole
# rows of the experts' result; ``_contrib_bd_flash_attention``'s head norms'
# gains (inputs 3, 4) scale the float32 norm before the operands are rounded
TARGET_DTYPE_KEEP = {
    "_contrib_moe_grouped_ffn": (2,),
    "_contrib_bd_flash_attention": (3, 4),
}

# numerically-sensitive ops forced to float32
FP32_OPS = [
    "softmax",
    "log_softmax",
    "softmin",
    "SoftmaxActivation",
    "SoftmaxOutput",
    "softmax_cross_entropy",
    "CTCLoss",
    "exp",
    "log",
    "log2",
    "log10",
    "log1p",
    "expm1",
    "logsumexp",
    "norm",
    "mean",
    "sum",
    "prod",
    "nansum",
    "nanprod",
    "cumsum",
    "erfinv",
    "gamma",
    "gammaln",
    "rsqrt",
    "rcbrt",
    "reciprocal",
    "_power",
    "broadcast_power",
    "_power_scalar",
    "_rpower_scalar",
    "_rdiv_scalar",
    "smooth_l1",
    "L2Normalization",
    "InstanceNorm",
    "LayerNorm",
    "GroupNorm",
    # norms stay f32: XLA fuses the f32 norm chain into the adjacent
    # matmuls and skips a convert round trip.  Chosen on an earlier
    # installation; every cell runs with it and none without (the other
    # side is not measured)
    "RMSNorm",
    # discrete or recurrent: a rounded router score flips an expert, and a
    # state-space layer's decays, dt and carried state compound over the
    # sequence (ops/moe.py, ops/ssm.py)
    "_contrib_moe_router_topk",
    "_contrib_ssd_scan",
    "_contrib_causal_conv1d",
    # the delta rule: the decay a channel, beta, the triangular solve and
    # the carried state (ops/kda.py; ``_contrib_kda_attention`` keeps its
    # inputs as they come and is float32 inside, decay and beta included)
    "_contrib_kda_scan",
    # NOT listed, like ``_contrib_kda_attention``: the rotary embedding of
    # adjacent pairs (ops/rotary.py, ``_contrib_rotary_embedding``) takes
    # its data as it comes and hands back the same dtype (a float32 copy of
    # every query head would double the traffic into the flash kernels);
    # its angles, sines and cosines are float32 from integer positions and
    # the rotation is float32 inside whatever the data's dtype
]

# multi-input ops whose inputs are cast to the widest participating dtype
WIDEST_TYPE_CASTS = [
    "elemwise_add",
    "elemwise_sub",
    "elemwise_mul",
    "elemwise_div",
    "broadcast_add",
    "broadcast_sub",
    "broadcast_mul",
    "broadcast_div",
    "broadcast_maximum",
    "broadcast_minimum",
    "broadcast_hypot",
    "_maximum",
    "_minimum",
    "_hypot",
    "concat",
    "stack",
    "where",
]
