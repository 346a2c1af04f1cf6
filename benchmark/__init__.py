"""The benchmark of lux_tpu: Graph500 and LDBC Graphalytics cells, run
one at a time by ``benchmark/run.py``.  It imports the system under test
only to drive it; the data, the references and the yardstick are its
own."""
