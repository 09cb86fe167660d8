"""Operations and bytes one ``layer_agg`` call needs: the masked weighted
mean over N client rows of an [N, R, D] float32 update stack, with an
[N, R] mask and [N] weights, written as an [R, D] result.  Counted on the
unpadded shapes: padding the kernel is handed is not work the algorithm
needs."""


def cost(N, R, D, itemsize=4):
    flops = 2.0 * N * R * D + 2.0 * N * R + R * D
    nbytes = float(itemsize) * (N * R * D + N * R + R * D)
    return flops, nbytes
