"""The chip benchmark of this repository: one command, cells named in
``BENCHMARK.json``, and every piece of a cell in a file of its own
(see ``spec.py``)."""
