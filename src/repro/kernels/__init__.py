"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships as <name>/{kernel,ops,ref}.py: pallas_call with explicit
BlockSpec VMEM tiling, a jit'd public wrapper, and a pure-jnp oracle the
tests sweep shapes/dtypes against (interpret=True on CPU).  The oracle of
``flash_attention`` is the model's own ``models.layers.attention_ref``."""
