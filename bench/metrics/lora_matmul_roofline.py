"""Share of its roofline that the fused LoRA-linear kernel
(``kernels/lora_matmul``) reaches over its executed calls in the traced
window. Moves ``round_s``."""
from bench import roofline
from bench.work import lora_matmul

# the HLO instruction that runs the kernel is named after its entry point
PATTERN = r"lora_matmul"


def read(ro):
    return roofline.kernel_share(ro, PATTERN, lora_matmul.from_operands)
