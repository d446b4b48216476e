"""The benchmark's own code: traffic generation, the load generator, the
reduction from records, counters and traces to metrics, the table of peaks,
the byte and operation counts, the plain reference and the comparison that
decides ``correct``. From the program under test it takes the entry points
(``main.main``, ``PipelineClient``, the stage engine) and nothing else."""
