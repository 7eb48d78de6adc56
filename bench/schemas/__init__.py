"""Schemas: ``bench/schemas/<schema>.py`` for a configuration whose
``schema`` key names it.  Each module gives ``generate(config, seed)`` (the
benchmark's data), ``to_program(data)`` (the same arrays as the program's
schema) and ``Reference(data)`` (the plain answers, with ``answer`` and
``cn_work``)."""
