"""The chip benchmark's own library: the yardstick that every cell is
measured with (traffic, trace reduction, operation counts, comparisons).

Nothing here imports the program under test except where a system
driver under ``models/`` builds it; the references under ``reference/``
import nothing of it at all.
"""
