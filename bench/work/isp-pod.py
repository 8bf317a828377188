"""isp-pod: the shared parameters read and written once; per pod, m, v and
the residual read and written and the gradient read."""


def update_bytes(leaves, workers: int) -> int:
    return sum(n * b * (2 + workers * (2 + 2 + 2 + 1)) for n, b in leaves)
