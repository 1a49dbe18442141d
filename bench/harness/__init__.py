"""The benchmark's harness: general code that every cell shares.

What belongs to one configuration, traffic mix, cell or metric lives in
files of its own under ``bench/`` (see ``harness.spec``)."""
