"""Share of its roofline that the ``cache_probe`` kernel reaches."""
from metrics import _kernels


def read(w):
    return _kernels.share(w, "cache_probe")
