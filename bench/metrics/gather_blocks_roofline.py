"""Share of its roofline that the ``gather_blocks`` kernel reaches."""
from metrics import _kernels


def read(w):
    return _kernels.share(w, "gather_blocks")
