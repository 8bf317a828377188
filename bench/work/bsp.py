"""bsp: parameters, m and v read and written, the gradient read."""


def update_bytes(leaves, workers: int) -> int:
    return sum(n * b * (2 + 2 + 2 + 1) for n, b in leaves)
