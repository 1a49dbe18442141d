"""One module per way of driving the system: ``mem`` (offline files
through ``Aligner.stream_sam``) and ``serve`` (the alignment service).
A configuration names its driver in its file (``"driver"``)."""
