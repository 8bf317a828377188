"""isp: bsp's count plus the residual read and written."""


def update_bytes(leaves, workers: int) -> int:
    return sum(n * b * (2 + 2 + 2 + 2 + 1) for n, b in leaves)
