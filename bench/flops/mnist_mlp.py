"""Forward multiply-accumulates per example of the paper's MNIST MLP,
784 -> 128 -> 256 -> 10 (arXiv 2209.06623 Sec. VI footnote 6)."""

LAYERS = [(784, 128), (128, 256), (256, 10)]


def forward_macs() -> int:
    return sum(a * b for a, b in LAYERS)


def forward_flops() -> int:
    return 2 * forward_macs()
