"""Kimi-VL-A3B-Instruct's MoonViT vision tower [vit] — arXiv:2504.07491,
https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct (config.json,
``vision_config``). A native-resolution ViT: 27 pre-norm blocks at
hidden 1152, 16 heads of 72, GELU-tanh MLP 4304, patch 14, a learned
64x64 position table resized to each image's grid, 2-D RoPE; then 2x2
patch merging and the MLP projector 4608 -> 4608 -> 2048 (the language
model's width). The language model itself is not part of this config.
"""
from repro.configs.base import ModelConfig, VisionConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="kimi-vl-a3b-moonvit", family="vit",
        n_layers=27, d_model=1152, n_heads=16, n_kv_heads=16, head_dim=72,
        d_ff=4304, vocab=0, norm="layernorm", act="gelu",
        rope_theta=10000.0, patch_size=14,
        vision=VisionConfig(pos_grid=64, merge=2, out_dim=2048),
        compute_dtype="float32", remat=False,
    )


def reduced() -> ModelConfig:
    return ModelConfig(
        name="kimi-vl-a3b-moonvit-reduced", family="vit",
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, vocab=0, norm="layernorm", act="gelu",
        rope_theta=10000.0, patch_size=4, vision=VisionConfig(pos_grid=8, merge=2, out_dim=32),
        compute_dtype="float32", remat=False,
    )
