"""The chip benchmark's library: discovery of cells, configurations, traffic
mixes, metric readers and architecture files (``archs/``) by name, the
seeded traffic generator, the client loop over the serving engine, the
plain reference that decides ``correct``, operation and byte counts with
the table of peaks, and the reduction of a profiler trace to device
times.

Nothing here is imported by the program under test, and nothing here
imports the program at module level: ``run.py`` puts ``src`` on the path
and hands the program in."""
