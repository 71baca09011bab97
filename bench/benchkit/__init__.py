"""The benchmark's own code: everything that decides what a cell measures
and whether its output is correct. The program under test is imported only
to build its engine and drive it (``repro.launch.serve.build_engine``,
``ServingEngine.submit`` / ``step``)."""
