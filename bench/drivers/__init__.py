"""General drivers of the benchmark's traffic: each reads the parameters
of a traffic file (``bench/traffic/<name>.json``) whose ``driver`` names
it, runs the program through its window and checks what it produced."""
