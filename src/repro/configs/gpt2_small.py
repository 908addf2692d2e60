"""GPT-2 Small (124M) [Radford et al. 2019], the paper's text backbone
(§4): 12L, d_model 768, 12 heads, d_ff 3072, vocab 50257, tied
embeddings.  It fits one TPU v5e chip whole at its published width and
depth."""
from repro.configs.paper_models import GPT2_SMALL, GPT_TINY

CONFIG = GPT2_SMALL
SMOKE = GPT_TINY
