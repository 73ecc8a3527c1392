"""General traffic generators. A traffic mix file names one in its
"driver" key and gives it parameters; every driver has the interface

    Driver(config, traffic, seed, devices)
    .setup()            build the deployment, write its data, warm up
    .step() -> int      one operation of the window; user bytes completed
    .span_points()      [(owner, attr, span name, bytes fn | None, block)]
                        the program calls a traced run puts spans around
    .counters() -> dict the store's counters, flat (or {} if none)
    .check() -> (checks, info)
                        checks: {name: (value, limit)}, compared once the
                        window has closed; info: printed, not compared
    .close()
"""
