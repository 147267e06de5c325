"""Share of its roofline that the flash_attention kernel reaches in the traced
slice (the shared reduction in `bench/roofline.py`, with the kernel's
work count in `bench/kernels/flash_attention.py`)."""
from roofline import kernel_roofline

NAME = "flash_attention_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "output_tokens_per_s"


def read(ctx):
    return kernel_roofline(ctx, "flash_attention")
