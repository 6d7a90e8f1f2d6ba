"""The yardstick: traffic, drivers, reduction, peaks and FLOP counts.

Nothing here is imported by the program; from the program this package
takes `ServingEngine`, `TrainEngine`, `AdamW`, `LlamaForCausalLM` and the
compile-cache switch, and nothing else.
"""
