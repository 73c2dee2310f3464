from repro.quant.qtensor import (
    QTensor,
    quantize,
    dequantize,
    quantize_tree,
    dense,
    quant_spec,
    build_variants,
)

__all__ = ["QTensor", "quantize", "dequantize", "quantize_tree", "dense", "quant_spec",
           "build_variants"]
