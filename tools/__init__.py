# Makes the helpers importable (tools.stepcost, tools.serve_bench) from
# chip_smoke.py, the tests and each other; the CLI scripts in here
# still run standalone.
