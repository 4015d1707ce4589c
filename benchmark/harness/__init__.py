"""The benchmark's yardstick: everything a later PR may not change.

``cells``   finds a cell's configuration, traffic mix and per-layer metrics
            by the names in ``BENCHMARK.json``;
``device``  refuses anything but the chips the cell asks for, counts
            compilations, reads the allocator's peak;
``stats``   medians, percentiles and the spread the bounds are set from;
``flops``   operations and bytes of the policy as functions of its shapes,
            and the table of peaks (``peaks.json``);
``trace``   the reduction from a profiler trace to busy time, idle gaps,
            scope shares and collective overlap;
``obs``     seeded observations for the comparison with the reference;
``compare`` that comparison, which decides ``correct`` for the policy;
``program`` from a cell's data files to the program's ``RunConfig``;
``result``  from a runner's record to the one line the driver reads.

From the program (``dotaclient_tpu``) the benchmark takes the system under
test, its counters and spans, and its kernel and scope names; nothing here
is imported by the program.
"""
