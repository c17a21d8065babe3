"""The yardstick: everything a later PR may not change.  Nothing here is
imported by the program; ``grammar.py`` and the plain references under
``references/`` import nothing of the program either.  Nothing here is
one model's: a configuration's file names its reference and its cost
model (``correct.py::reference_for``, ``costs.py::module_for``)."""
