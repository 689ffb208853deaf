"""Share of its roofline that the flash-attention kernel
(``kernels/flash_attention``) reaches over its executed calls in the
traced window. Moves ``round_s``."""
from bench import roofline
from bench.work import flash_attention

# the HLO instruction that runs the kernel is named after its entry point
PATTERN = r"_fa_jit"


def read(ro):
    return roofline.kernel_share(ro, PATTERN, flash_attention.from_operands)
