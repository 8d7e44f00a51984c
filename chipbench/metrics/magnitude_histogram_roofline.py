"""Share of its roofline that the ``magnitude_histogram`` kernel reached in the
traced window (see ``chipbench/kernels.py``)."""

from chipbench import kernels as K

read = K.roofline_reader("magnitude_histogram")
