"""granite-20b [dense] — GPTBigCode: pre-norm LayerNorm with bias, one fused
c_attn giving 48 query heads of 128 and a single K/V head (MQA, kv=1),
tanh-GELU MLP with biases, learned absolute positions, code.
[arXiv:2405.04324; hf:ibm-granite/granite-20b-code-base-8k]

The plain layer reference is ``repro.models.gptbigcode_ref``.  The model
stack of ``repro.models`` knows only its generic blocks (RMSNorm, rotary,
SwiGLU), so it runs these widths but not GPTBigCode's layer."""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="granite-20b", family="dense",
    num_layers=52, d_model=6144, num_heads=48, num_kv_heads=1,
    d_ff=24576, vocab_size=49152, head_dim=128,
)
