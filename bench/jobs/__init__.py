"""The benchmark's jobs, one module per `job` named in a workload file.

A job module has three functions:
    setup(cell, seed) -> ctx      build the inputs and the system under
                                  test, and warm every shape the window
                                  uses;
    window(ctx, seconds) -> rec   drive the timed path for `seconds`;
    verify(ctx) -> numbers        free the system under test, run the
                                  plain reference and return each
                                  compared number by name.
"""
