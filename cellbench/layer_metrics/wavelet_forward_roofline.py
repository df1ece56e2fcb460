"""Share of its roofline that ``wavelet_forward`` reached: least time from
``cellbench/roofline.py`` over the kernel's device time in the trace."""
from cellbench import roofline


def read(obs):
    return roofline.share(obs, "wavelet_forward")
