"""Model FLOPs per configuration: ``bench/counts/models/<reference>.py``
for the ``reference`` name in the configuration's file, found by that
name when the cell is loaded (``bench/harness.py::model_module``)."""
