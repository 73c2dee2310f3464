"""Roofline share of the Q8 matmul kernel (`quant_matmul` calls with int8
weights) over the traced window, in %: operations and bytes per call from
its shapes (`bench/flops.py`), over the device time of its calls."""
from bench import flops
from bench.metrics._common import kernel_roofline, quant_matmul_call


def _cost(shp):
    (_, (M, N)), (_, (_, K)) = shp[0], shp[1]
    return flops.q8_matmul(M, K, N)


def read(run):
    return kernel_roofline(run, lambda h: quant_matmul_call(h, "s8"), _cost)
