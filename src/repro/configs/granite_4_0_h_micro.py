"""granite-4.0-h-micro [hybrid] — Mamba-2 layers with one NoPE GQA layer
in every ten, dense SwiGLU after every mixer, µP multipliers
(https://huggingface.co/ibm-granite/granite-4.0-h-micro, config.json:
``model_type`` granitemoehybrid with 0 experts).

40L d_model=2048, attention at layers 5/15/25/35 (32 query, 8 KV heads of
64, no rotary), Mamba-2 64 heads x 64 with d_state 128, one group, conv 4
with bias, chunk 256; d_ff=8192, RMSNorm eps 1e-5, tied embeddings,
vocab 100,352; embeddings x12, residual branches x0.22, attention scale
1/64, logits /8.
"""

from repro.config import ATTN, DENSE_FFN, MAMBA, MambaConfig, ModelConfig
from repro.configs._base import experiment, smoke_experiment

#: one period of the layer pattern: five Mamba-2 layers, attention, four
PERIOD = (MAMBA,) * 5 + (ATTN,) + (MAMBA,) * 4


def get_config():
    model = ModelConfig(
        name="granite-4.0-h-micro",
        family="hybrid",
        vocab_size=100352,
        d_model=2048,
        n_layers=40,
        n_heads=32,
        n_kv_heads=8,
        d_ff=8192,
        norm_eps=1e-5,
        tie_embeddings=True,
        rope=False,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        attention_multiplier=0.015625,
        logits_scaling=8.0,
        layer_pattern=PERIOD,
        ffn_pattern=(DENSE_FFN,),
        mamba=MambaConfig(d_state=128, d_conv=4, expand=2, head_dim=64,
                          chunk_size=256),
        max_seq_len=131072,
        source="https://huggingface.co/ibm-granite/granite-4.0-h-micro"
               "/blob/main/config.json",
    )
    return experiment(
        model,
        notes="hybrid: 4 NoPE GQA layers of 40, one per 10-layer period")


def get_smoke_config():
    # the hybrid character at two layers: one Mamba-2, one GQA layer
    return smoke_experiment(get_config(), layer_pattern=(MAMBA, ATTN),
                            n_layers=2, n_kv_heads=2)
