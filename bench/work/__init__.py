"""Per-mode counts of the bytes a step's update phase must move.

``bench/work/<mode>.py`` defines ``update_bytes(leaves, workers)``: leaves
is a list of (entries, bytes per entry) of the parameter tree.  The count
is what the phase needs, not what an implementation moves: each parameter,
Adam moment and residual entry read and written once, each gradient entry
read once.  A cell whose mode has no file here fails.
"""
