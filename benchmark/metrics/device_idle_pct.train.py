from benchmark.lib import readers

read = readers.idle_pct
