"""Qwen3 (hf:Qwen/Qwen3-0.6B): RMSNorm before attention and MLP, q and k
RMS-normalised per head before the rotary embedding, grouped-query
attention, SwiGLU MLP. The equations are in ``dense.py``."""
FLAGS = {"norm": "rmsnorm", "qk_norm": True}
