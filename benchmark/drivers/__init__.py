"""One driver per traffic ``mode``: how a cell's games in flight play one
round.  ``lib/window.py`` finds the module by the traffic file's
``mode`` and drives ``Driver(system)`` through ``draw``, ``play`` and
``clean``; a later mode is one more file here."""
