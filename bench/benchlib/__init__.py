"""The benchmark's yardstick, kept apart from the program under test.

``spec`` finds cells, configurations, traffic and metric readers by
name; ``points`` generates the inputs; ``runner`` runs one cell once;
``reference`` and ``compare`` decide ``correct``; ``devtrace`` reduces
the profiler's trace to device busy time, idle gaps and program time.
"""
