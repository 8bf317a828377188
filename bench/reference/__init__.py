"""Plain float32 reference of the benchmark's models and their update.

Each block kind is a module of its own (``layernorm``, ``rmsnorm``,
``gelu_ff``, ``swiglu``, ``rope_gqa_causal``, ``tied_embedding``); a
configuration file names the kinds it is built from, and ``model`` finds
them by name.  ``update`` holds the optimizer side: clip, Adam, the
significance split and the block top-k combine.  Nothing here imports the
program under test.
"""
