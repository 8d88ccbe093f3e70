"""Plain numpy references, one module per system kind.

A reference recomputes what each served value must be from the seed and the
request alone (``values``); it imports nothing of the program and takes
nothing the program made.  Each module has ``expected(cfg, seed, items)``,
the outputs a correct system returns for one token's item ids, and
``compare(got, want)``, the numbers compared, each with limit 0.
"""
