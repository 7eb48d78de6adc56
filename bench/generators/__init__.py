"""Traffic generators: ``bench/generators/<generator>.py`` for a mix file
(``bench/mixes/<mix>.json``) whose ``generator`` key names it.  Each module
gives ``make(mix, data, rng)``, returning an object with ``clients``,
``warmup()`` (rounds of keyword sets, each round sent together, so that
every batch the window can form compiles in set-up) and ``client(i)``
(client ``i``'s keyword sets, in order), and ``describe()`` (what the
run prints of its traffic on an earlier line)."""
