"""whisper-small (Radford et al., arXiv:2212.04356; openai/whisper-small):
encoder-decoder, 12+12 pre-LN layers, d_model 768, 12 heads of 64, MLP 3072
with exact GELU, vocabulary 51865, 80 log-mel bins over 3000 frames (30 s)
into a conv stem that halves them to 1500 encoder positions, 448 learned
decoder positions, biases on every dense layer but the key projections, no
rotary embedding, the head tied to the token embedding."""
from .base import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        arch_id="whisper-small",
        family="audio",
        n_layers=12,
        d_model=768,
        n_heads=12,
        n_kv_heads=12,
        d_ff=3072,
        vocab_size=51865,
        is_encoder_decoder=True,
        n_encoder_layers=12,
        n_audio_frames=1500,
        n_mels=80,
        max_target_positions=448,
        rope_fraction=0.0,
        tie_embeddings=True,
        mlp_act="gelu",
        norm_kind="layernorm",
        dtype="bfloat16",
        source="[arXiv:2212.04356]",
        notes="published architecture; the model is built by "
              "models/transformer.py::build_encdec_model",
    )


def smoke_config() -> ModelConfig:
    return config().replace(
        n_layers=2, n_encoder_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=256, vocab_size=512, n_audio_frames=32, dtype="float32",
    )
