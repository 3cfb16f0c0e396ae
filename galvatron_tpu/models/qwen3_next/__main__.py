from galvatron_tpu.models.qwen3_next import main

raise SystemExit(main())
