"""BLAST reproduction package: structured linears (``core``), Pallas TPU
kernels (``kernels``), models, the serving engine (``serve``) and the
launchers (``launch``)."""
