"""The yardstick: traffic, drivers, reduction, peaks and FLOP counts.

Nothing here is imported by the program; from the program this package
takes `ServingEngine`, `TrainEngine`, `AdamW`, the compile-cache switch
and, through the configuration's family (`benchmark/families`), the model
class, and nothing else.
"""
