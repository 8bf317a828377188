"""Plain float32 reference of the benchmark's models and their update.

Each block kind is a module of its own (``layernorm``, ``rmsnorm``,
``gelu_ff``, ``swiglu``, ``moe_swiglu``, ``rope_gqa_causal`` with its
optional sliding window, ``tied_embedding``, ``untied_embedding``); a
configuration file names the kinds it is built from, and ``model`` finds
them by name and gives them the file's sizes.  ``update`` holds the
optimizer side: clip, Adam, the significance split and the block top-k
combine.  Nothing here imports the program under test.
"""
