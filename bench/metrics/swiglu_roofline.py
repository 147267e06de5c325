"""Share of its roofline that the swiglu kernel reaches in the traced
slice (the shared reduction in `bench/roofline.py`, with the kernel's
work count in `bench/kernels/swiglu.py`)."""
from roofline import kernel_roofline

NAME = "swiglu_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "output_tokens_per_s"


def read(ctx):
    return kernel_roofline(ctx, "swiglu")
