"""Share of its roofline that the ``hybrid_compress`` kernel reached in the
traced window (see ``chipbench/kernels.py``)."""

from chipbench import kernels as K

read = K.roofline_reader("hybrid_compress")
