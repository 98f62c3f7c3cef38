"""One module per kind of model, found by a configuration's ``kind``.

Each holds all the benchmark knows of that kind: ``init`` (its seeded
weights, laid out as the program lays out its parameters), ``reference``
(its plain float32 model in ``chipbench/reference/``) and the operations and
bytes its work requires. A new kind of model adds a file here.
"""
