"""Adapters from a cell to the program, one module per system kind.

A configuration file names its kind (``"system"``); ``systems/<kind>.py``
defines ``System(cfg, seed)`` with:

``n_items``          items the traffic draws from.
``kernel_shapes``    ``lanes -> {kernel: shapes of one call}`` for a token.
``request(items)``   the device input of one token (the program sees only it).
``submit(req)``      the timed submit call; returns a handle.
``wait(handle)``     the timed wait call; returns a dict of device arrays.
``counters()``       the program's ``IOMetrics`` fields, on the host.
"""
