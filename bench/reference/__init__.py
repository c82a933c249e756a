"""Plain float32 forward passes of the benchmark's configurations.

One module per architecture, named in a configuration file's ``reference``
key.  Each exposes ``logits_at(params, sizes, tokens, positions, quant=None)``
and imports nothing of the program under test: it reads the weights the
benchmark made (bench/harness/weights.py) and the sizes of the configuration
file.
"""
