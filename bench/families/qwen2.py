"""The Qwen2 family (Hugging Face ``Qwen2ForCausalLM``) as the program runs
it: a dense GQA decoder with a bias on the q, k and v projections and no
norm on q and k. What a family file may hold is in ``bench/model.py``."""

PROGRAM = {"family": "dense", "qkv_bias": True, "qk_norm": False}
