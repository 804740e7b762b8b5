"""The benchmark's harness: drivers, traffic, trace reduction, peaks, checks.

Nothing here is imported by the program (``kubeflow_tpu``); the harness
imports the program only inside the drivers, as the system under test.
"""
