"""Drivers: how a KIND of configuration is run (``train``, ``serve``), found by
the ``driver`` a configuration file names.  ``run(**kw) -> obs``: the bag of
raw observations the readers take metrics from."""
