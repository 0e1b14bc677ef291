"""The benchmark of the ExSample search engine on TPU (``bench/run.py``)."""
