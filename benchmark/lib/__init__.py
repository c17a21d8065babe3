"""The yardstick: everything a later PR may not change.  Nothing here is
imported by the program; ``reference.py`` and ``grammar.py`` import
nothing of the program either."""
