"""HBM roofline share of the p(l)-CG iteration: useful iterations times
``bench/roofline.py`` bytes at 819 GB/s, over device busy time (engine
and kernels layer)."""
from bench.readers import body_roofline as read  # noqa: F401
