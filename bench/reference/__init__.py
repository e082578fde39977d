"""Plain references, one file per PLA method and per wire protocol.

A method file has ``run(ts, ys, eps, max_run) -> MethodOutput``; a
protocol file has ``decode(bytes, ts)`` and ``expected(output, ts, ys)``,
each giving ``(values, shape)``.  They import nothing of the system
under test.
"""
