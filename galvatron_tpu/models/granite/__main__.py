from galvatron_tpu.models.granite import main

raise SystemExit(main())
