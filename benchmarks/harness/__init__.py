"""The benchmark's yardstick: traffic generation, the HTTP client, the
reduction from traces, spans and counters to metrics, the table of peaks,
the roofline arithmetic, the plain references and the comparison that
decides `correct`. From the program it takes only the system under test
and its spans, counters and kernel names."""
